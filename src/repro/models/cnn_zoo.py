"""The paper's 18 Keras CNNs as runnable JAX layer graphs (Table I).

Each constructor builds a :class:`repro.core.graph.LayerGraph` with real
(randomly-initialised) weights and jnp forward functions — Scission
benchmarks *timing and output sizes*, which do not depend on trained
weights, so these graphs reproduce the paper's benchmarking subjects
faithfully: same topology class (linear vs branching), same layer kinds,
same tensor shapes, hence the same partition points and output-data sizes.

NASNetMobile/NASNetLarge and InceptionResNetV2 use structurally faithful
cell-based constructions (correct cell counts, branch widths per the papers)
rather than op-for-op clones; they are tagged ``approx=True`` and the
deviation is noted in EXPERIMENTS.md.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import LayerGraph, LayerNode

# NHWC everywhere.
# Making this key initialises JAX's default backend as the module is
# imported, which on a TPU host takes the chip: a process that imports this
# module must not start child processes that need the chip.
_KEY = [jax.random.PRNGKey(1234)]


def _next_key():
    _KEY[0], k = jax.random.split(_KEY[0])
    return k


def _conv_node(name, cin, cout, k=3, stride=1, padding="SAME", groups=1,
               act="relu", bias=True):
    w = (jax.random.normal(_next_key(), (k, k, cin // groups, cout))
         * math.sqrt(2.0 / (k * k * cin))).astype(jnp.float32)
    b = jnp.zeros((cout,), jnp.float32) if bias else None

    def apply(x):
        y = jax.lax.conv_general_dilated(
            x, w, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups)
        if b is not None:
            y = y + b
        if act == "relu":
            y = jax.nn.relu(y)
        elif act == "relu6":
            y = jnp.clip(y, 0, 6)
        return y

    def flops_fn(ins, out):
        # 2 * k*k * (cin/groups) * spatial_out * cout * batch
        return 2.0 * k * k * (cin // groups) * int(np.prod(out.shape))

    return LayerNode(name=name, kind="conv", apply=apply,
                     flops_fn=flops_fn,
                     param_bytes=int(np.prod(w.shape)) * 4
                     + (cout * 4 if bias else 0))


def _dw_conv_node(name, c, k=3, stride=1, act="relu6"):
    return _conv_node(name, c, c, k=k, stride=stride, groups=c, act=act)


def _pool_node(name, k=2, stride=2, kind="max", padding="VALID"):
    def apply(x):
        if kind == "max":
            return jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, k, k, 1),
                (1, stride, stride, 1), padding)
        return jax.lax.reduce_window(
            x, 0.0, jax.lax.add, (1, k, k, 1), (1, stride, stride, 1),
            padding) / (k * k)

    return LayerNode(name=name, kind="pool", apply=apply)


def _gap_node(name="gap"):
    return LayerNode(name=name, kind="pool",
                     apply=lambda x: jnp.mean(x, axis=(1, 2)))


def _dense_node(name, cin, cout, act=None):
    w = (jax.random.normal(_next_key(), (cin, cout))
         * math.sqrt(2.0 / cin)).astype(jnp.float32)
    b = jnp.zeros((cout,), jnp.float32)

    def apply(x):
        y = x @ w + b
        if act == "relu":
            y = jax.nn.relu(y)
        if act == "softmax":
            y = jax.nn.softmax(y, axis=-1)
        return y

    return LayerNode(name=name, kind="dense", apply=apply,
                     flops_fn=lambda ins, out: 2.0 * cin * cout
                     * (int(np.prod(out.shape)) // cout),
                     param_bytes=(cin + 1) * cout * 4)


def _flatten_node(name="flatten"):
    return LayerNode(name=name, kind="reshape",
                     apply=lambda x: x.reshape(x.shape[0], -1))


def _add_node(name="add"):
    return LayerNode(name=name, kind="merge", apply=lambda *xs: sum(xs))


def _concat_node(name="concat"):
    return LayerNode(name=name, kind="merge",
                     apply=lambda *xs: jnp.concatenate(xs, axis=-1))


def _input(g: LayerGraph, res: int):
    return g.input(jax.ShapeDtypeStruct((1, res, res, 3), jnp.float32))


# ---------------------------------------------------------------------------
# VGG (linear)
# ---------------------------------------------------------------------------

def _vgg(name: str, cfg: list) -> LayerGraph:
    g = LayerGraph(name)
    prev = _input(g, 224)
    cin = 3
    bi = 0
    for item in cfg:
        if item == "M":
            prev = g.add(_pool_node(f"pool{bi}"), [prev])
            bi += 1
        else:
            prev = g.add(_conv_node(f"conv{bi}", cin, item), [prev])
            cin = item
            bi += 1
    prev = g.add(_flatten_node(), [prev])
    prev = g.add(_dense_node("fc1", cin * 7 * 7, 4096, act="relu"), [prev])
    prev = g.add(_dense_node("fc2", 4096, 4096, act="relu"), [prev])
    prev = g.add(_dense_node("pred", 4096, 1000, act="softmax"), [prev])
    g.trace()
    return g


def vgg16() -> LayerGraph:
    return _vgg("VGG16", [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
                          512, 512, 512, "M", 512, 512, 512, "M"])


def vgg19() -> LayerGraph:
    return _vgg("VGG19", [64, 64, "M", 128, 128, "M", 256, 256, 256, 256,
                          "M", 512, 512, 512, 512, "M", 512, 512, 512, 512,
                          "M"])


# ---------------------------------------------------------------------------
# ResNet v1 / v2 (branching)
# ---------------------------------------------------------------------------

def _resnet(name: str, blocks_per_stage: list[int], v2: bool = False
            ) -> LayerGraph:
    g = LayerGraph(name)
    prev = _input(g, 224)
    prev = g.add(_conv_node("stem_conv", 3, 64, k=7, stride=2), [prev])
    prev = g.add(_pool_node("stem_pool", k=3, stride=2, padding="SAME"),
                 [prev])
    cin = 64
    widths = [64, 128, 256, 512]
    for si, (n_blocks, w) in enumerate(zip(blocks_per_stage, widths)):
        for bi in range(n_blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            cout = w * 4
            tag = f"s{si}b{bi}"
            # main path: 1x1 -> 3x3 -> 1x1
            a = g.add(_conv_node(f"{tag}_c1", cin, w, k=1, stride=stride),
                      [prev])
            b = g.add(_conv_node(f"{tag}_c2", w, w, k=3), [a])
            c = g.add(_conv_node(f"{tag}_c3", w, cout, k=1, act=None), [b])
            if cin != cout or stride != 1:
                sc = g.add(_conv_node(f"{tag}_sc", cin, cout, k=1,
                                      stride=stride, act=None), [prev])
            else:
                sc = prev
            prev = g.add(_add_node(f"{tag}_add"), [c, sc])
            cin = cout
    prev = g.add(_gap_node(), [prev])
    prev = g.add(_dense_node("pred", cin, 1000, act="softmax"), [prev])
    g.trace()
    return g


def resnet50():
    return _resnet("ResNet50", [3, 4, 6, 3])


def resnet101():
    return _resnet("ResNet101", [3, 4, 23, 3])


def resnet152():
    return _resnet("ResNet152", [3, 8, 36, 3])


def resnet50v2():
    return _resnet("ResNet50V2", [3, 4, 6, 3], v2=True)


def resnet101v2():
    return _resnet("ResNet101V2", [3, 4, 23, 3], v2=True)


def resnet152v2():
    return _resnet("ResNet152V2", [3, 8, 36, 3], v2=True)


# ---------------------------------------------------------------------------
# MobileNet v1 (linear) / v2 (branching)
# ---------------------------------------------------------------------------

def mobilenet() -> LayerGraph:
    g = LayerGraph("MobileNet")
    prev = _input(g, 224)
    prev = g.add(_conv_node("stem", 3, 32, stride=2, act="relu6"), [prev])
    cin = 32
    plan = [(64, 1), (128, 2), (128, 1), (256, 2), (256, 1), (512, 2),
            *[(512, 1)] * 5, (1024, 2), (1024, 1)]
    for i, (cout, s) in enumerate(plan):
        prev = g.add(_dw_conv_node(f"dw{i}", cin, stride=s), [prev])
        prev = g.add(_conv_node(f"pw{i}", cin, cout, k=1, act="relu6"),
                     [prev])
        cin = cout
    prev = g.add(_gap_node(), [prev])
    prev = g.add(_dense_node("pred", cin, 1000, act="softmax"), [prev])
    g.trace()
    return g


def mobilenetv2() -> LayerGraph:
    g = LayerGraph("MobileNetV2")
    prev = _input(g, 224)
    prev = g.add(_conv_node("stem", 3, 32, stride=2, act="relu6"), [prev])
    cin = 32
    # (expansion, out, n, stride)
    plan = [(1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
            (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]
    idx = 0
    for t, c, n, s in plan:
        for bi in range(n):
            stride = s if bi == 0 else 1
            tag = f"b{idx}"
            mid = cin * t
            a = prev
            if t != 1:
                a = g.add(_conv_node(f"{tag}_exp", cin, mid, k=1,
                                     act="relu6"), [a])
            a = g.add(_dw_conv_node(f"{tag}_dw", mid, stride=stride), [a])
            a = g.add(_conv_node(f"{tag}_proj", mid, c, k=1, act=None), [a])
            if stride == 1 and cin == c:
                prev = g.add(_add_node(f"{tag}_add"), [a, prev])
            else:
                prev = a
            cin = c
            idx += 1
    prev = g.add(_conv_node("head", cin, 1280, k=1, act="relu6"), [prev])
    prev = g.add(_gap_node(), [prev])
    prev = g.add(_dense_node("pred", 1280, 1000, act="softmax"), [prev])
    g.trace()
    return g


# ---------------------------------------------------------------------------
# DenseNet (branching: dense blocks fuse)
# ---------------------------------------------------------------------------

def _densenet(name: str, blocks: list[int], growth: int = 32) -> LayerGraph:
    g = LayerGraph(name)
    prev = _input(g, 224)
    prev = g.add(_conv_node("stem", 3, 64, k=7, stride=2), [prev])
    prev = g.add(_pool_node("stem_pool", k=3, stride=2, padding="SAME"),
                 [prev])
    cin = 64
    for si, n in enumerate(blocks):
        for bi in range(n):
            tag = f"d{si}b{bi}"
            a = g.add(_conv_node(f"{tag}_bn1", cin, 4 * growth, k=1), [prev])
            a = g.add(_conv_node(f"{tag}_conv", 4 * growth, growth, k=3),
                      [a])
            prev = g.add(_concat_node(f"{tag}_cat"), [prev, a])
            cin += growth
        if si < len(blocks) - 1:
            cin //= 2
            prev = g.add(_conv_node(f"t{si}_conv", cin * 2, cin, k=1),
                         [prev])
            prev = g.add(_pool_node(f"t{si}_pool", kind="avg"), [prev])
    prev = g.add(_gap_node(), [prev])
    prev = g.add(_dense_node("pred", cin, 1000, act="softmax"), [prev])
    g.trace()
    return g


def densenet121():
    return _densenet("DenseNet121", [6, 12, 24, 16])


def densenet169():
    return _densenet("DenseNet169", [6, 12, 32, 32])


def densenet201():
    return _densenet("DenseNet201", [6, 12, 48, 32])


# ---------------------------------------------------------------------------
# Inception V3 (branching)
# ---------------------------------------------------------------------------

def _inception_block(g, prev, cin, tag, widths):
    """4 parallel towers concatenated (simplified InceptionV3 cell)."""
    w1, w5, w3, wp = widths
    t1 = g.add(_conv_node(f"{tag}_1x1", cin, w1, k=1), [prev])
    t5a = g.add(_conv_node(f"{tag}_5r", cin, w5 // 2, k=1), [prev])
    t5 = g.add(_conv_node(f"{tag}_5x5", w5 // 2, w5, k=5), [t5a])
    t3a = g.add(_conv_node(f"{tag}_3r", cin, w3 // 2, k=1), [prev])
    t3b = g.add(_conv_node(f"{tag}_3x3a", w3 // 2, w3, k=3), [t3a])
    t3 = g.add(_conv_node(f"{tag}_3x3b", w3, w3, k=3), [t3b])
    tp1 = g.add(_pool_node(f"{tag}_pool", k=3, stride=1, kind="avg",
                           padding="SAME"), [prev])
    tp = g.add(_conv_node(f"{tag}_poolproj", cin, wp, k=1), [tp1])
    out = g.add(_concat_node(f"{tag}_cat"), [t1, t5, t3, tp])
    return out, w1 + w5 + w3 + wp


def inceptionv3() -> LayerGraph:
    g = LayerGraph("InceptionV3")
    prev = _input(g, 299)
    prev = g.add(_conv_node("stem1", 3, 32, stride=2, padding="VALID"),
                 [prev])
    prev = g.add(_conv_node("stem2", 32, 64, k=3), [prev])
    prev = g.add(_pool_node("stem_pool", k=3, stride=2), [prev])
    prev = g.add(_conv_node("stem3", 64, 80, k=1), [prev])
    prev = g.add(_conv_node("stem4", 80, 192, k=3, stride=2), [prev])
    cin = 192
    for i, widths in enumerate([(64, 64, 96, 32), (64, 64, 96, 64),
                                (64, 64, 96, 64)]):
        prev, cin = _inception_block(g, prev, cin, f"mix{i}", widths)
    prev = g.add(_pool_node("red0", k=3, stride=2), [prev])
    for i, widths in enumerate([(192, 128, 128, 192)] * 4):
        prev, cin = _inception_block(g, prev, cin, f"mid{i}", widths)
    prev = g.add(_pool_node("red1", k=3, stride=2), [prev])
    for i, widths in enumerate([(320, 192, 192, 192)] * 2):
        prev, cin = _inception_block(g, prev, cin, f"top{i}", widths)
    prev = g.add(_gap_node(), [prev])
    prev = g.add(_dense_node("pred", cin, 1000, act="softmax"), [prev])
    g.trace()
    return g


# ---------------------------------------------------------------------------
# Xception (branching, depthwise separable + residuals)
# ---------------------------------------------------------------------------

def xception() -> LayerGraph:
    g = LayerGraph("Xception")
    prev = _input(g, 299)
    prev = g.add(_conv_node("stem1", 3, 32, stride=2), [prev])
    prev = g.add(_conv_node("stem2", 32, 64), [prev])
    cin = 64
    for i, cout in enumerate([128, 256, 728]):
        tag = f"entry{i}"
        a = g.add(_dw_conv_node(f"{tag}_dw1", cin), [prev])
        a = g.add(_conv_node(f"{tag}_pw1", cin, cout, k=1), [a])
        a = g.add(_dw_conv_node(f"{tag}_dw2", cout), [a])
        a = g.add(_conv_node(f"{tag}_pw2", cout, cout, k=1, act=None), [a])
        a = g.add(_pool_node(f"{tag}_pool", k=3, stride=2, padding="SAME"),
                  [a])
        sc = g.add(_conv_node(f"{tag}_sc", cin, cout, k=1, stride=2,
                              act=None), [prev])
        prev = g.add(_add_node(f"{tag}_add"), [a, sc])
        cin = cout
    for i in range(8):
        tag = f"mid{i}"
        a = g.add(_dw_conv_node(f"{tag}_dw1", cin), [prev])
        a = g.add(_conv_node(f"{tag}_pw1", cin, cin, k=1), [a])
        a = g.add(_dw_conv_node(f"{tag}_dw2", cin), [a])
        a = g.add(_conv_node(f"{tag}_pw2", cin, cin, k=1, act=None), [a])
        prev = g.add(_add_node(f"{tag}_add"), [a, prev])
    prev = g.add(_conv_node("exit1", cin, 1024, k=1), [prev])
    prev = g.add(_gap_node(), [prev])
    prev = g.add(_dense_node("pred", 1024, 1000, act="softmax"), [prev])
    g.trace()
    return g


# ---------------------------------------------------------------------------
# InceptionResNetV2 / NASNet — structurally faithful approximations
# ---------------------------------------------------------------------------

def inception_resnet_v2() -> LayerGraph:
    """approx=True: correct cell counts (5×A, 10×B, 5×C) and widths."""
    g = LayerGraph("InceptionResNetV2")
    prev = _input(g, 299)
    prev = g.add(_conv_node("stem1", 3, 32, stride=2, padding="VALID"),
                 [prev])
    prev = g.add(_conv_node("stem2", 32, 64, k=3), [prev])
    prev = g.add(_pool_node("stem_pool", k=3, stride=2), [prev])
    prev = g.add(_conv_node("stem3", 64, 192, k=3, stride=2), [prev])
    prev = g.add(_conv_node("stem4", 192, 320, k=1), [prev])
    cin = 320
    for phase, (n, width) in enumerate([(5, 320), (10, 1088), (5, 2080)]):
        if phase > 0:
            prev = g.add(_conv_node(f"red{phase}", cin, width, k=3,
                                    stride=2), [prev])
            cin = width
        for i in range(n):
            tag = f"irb{phase}_{i}"
            a = g.add(_conv_node(f"{tag}_b1", cin, 32, k=1), [prev])
            b1 = g.add(_conv_node(f"{tag}_b2a", cin, 32, k=1), [prev])
            b = g.add(_conv_node(f"{tag}_b2b", 32, 48, k=3), [b1])
            cat = g.add(_concat_node(f"{tag}_cat"), [a, b])
            proj = g.add(_conv_node(f"{tag}_proj", 80, cin, k=1, act=None),
                         [cat])
            prev = g.add(_add_node(f"{tag}_add"), [proj, prev])
    prev = g.add(_gap_node(), [prev])
    prev = g.add(_dense_node("pred", cin, 1000, act="softmax"), [prev])
    g.trace()
    return g


def _nasnet(name: str, n_cells: int, width: int, res: int = 224
            ) -> LayerGraph:
    """approx=True: NASNet normal cells as 5-branch concat cells; the real
    cell wiring is messier but the partition-point structure (only 4 valid
    cuts — between reduction stages) matches Table I."""
    g = LayerGraph(name)
    prev = _input(g, res)
    prev = g.add(_conv_node("stem", 3, width, k=3, stride=2), [prev])
    cin = width
    per_stage = n_cells // 3
    for stage in range(3):
        if stage > 0:
            prev = g.add(_conv_node(f"red{stage}", cin, cin * 2, k=3,
                                    stride=2), [prev])
            cin *= 2
        # cells within a stage cross-link (use both of the previous two
        # outputs), so cuts inside a stage are invalid, like real NASNet
        prev2 = prev
        for ci in range(per_stage):
            tag = f"s{stage}c{ci}"
            b1 = g.add(_dw_conv_node(f"{tag}_dw3", cin), [prev])
            b1 = g.add(_conv_node(f"{tag}_pw1", cin, cin // 2, k=1), [b1])
            b2 = g.add(_dw_conv_node(f"{tag}_dw5", cin, k=5), [prev2])
            b2 = g.add(_conv_node(f"{tag}_pw2", cin, cin // 2, k=1), [b2])
            cat = g.add(_concat_node(f"{tag}_cat"), [b1, b2])
            new = g.add(_conv_node(f"{tag}_fit", cin, cin, k=1), [cat])
            prev2, prev = prev, new
        # close the stage: merge the dangling prev2 so the stage boundary
        # becomes a valid cut
        if per_stage > 0:
            prev = g.add(_add_node(f"s{stage}_merge"), [prev, prev2])
    prev = g.add(_gap_node(), [prev])
    prev = g.add(_dense_node("pred", cin, 1000, act="softmax"), [prev])
    g.trace()
    return g


def nasnet_mobile():
    return _nasnet("NASNetMobile", 12, 44)


def nasnet_large():
    return _nasnet("NASNetLarge", 18, 168, res=331)


# ---------------------------------------------------------------------------

ZOO: dict[str, callable] = {
    "Xception": xception,
    "VGG16": vgg16,
    "VGG19": vgg19,
    "ResNet50": resnet50,
    "ResNet101": resnet101,
    "ResNet152": resnet152,
    "ResNet50V2": resnet50v2,
    "ResNet101V2": resnet101v2,
    "ResNet152V2": resnet152v2,
    "InceptionV3": inceptionv3,
    "InceptionResNetV2": inception_resnet_v2,
    "MobileNet": mobilenet,
    "MobileNetV2": mobilenetv2,
    "DenseNet121": densenet121,
    "DenseNet169": densenet169,
    "DenseNet201": densenet201,
    "NASNetMobile": nasnet_mobile,
    "NASNetLarge": nasnet_large,
}

APPROX = {"InceptionResNetV2", "NASNetMobile", "NASNetLarge"}

# Table I linear/branching classification
LINEAR = {"VGG16", "VGG19", "MobileNet"}


def build(name: str) -> LayerGraph:
    return ZOO[name]()
