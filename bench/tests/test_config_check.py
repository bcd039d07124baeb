"""``check_config`` holds the program to every key of the configuration's
``model`` section that the reference reads, and refuses a program field
set away from the plain block that no key covers."""

import dataclasses
import json
from pathlib import Path

import pytest

import tiny
from drivers.lm_serving import check_config

CONFIGS = Path(__file__).parents[1] / "configs"


def program(conf: dict, **over):
    from repro.models import get_config
    return get_config(conf["arch"]).replace(**dict(conf["overrides"],
                                                   **over))


def refusal(cfg, model: dict) -> str:
    with pytest.raises(SystemExit) as e:
        check_config(cfg, model)
    return str(e.value)


@pytest.mark.parametrize("name", ["granite-moe-3b-a800m", "granite-8b-half"])
def test_the_accepted_configuration_files_pass(name):
    conf = json.loads((CONFIGS / f"{name}.json").read_text())
    check_config(program(conf), conf["model"])


def test_the_variant_passes_with_every_key_stated():
    check_config(program(tiny.VARIANT_CONF), tiny.VARIANT_CONF["model"])
    # the defaults, stated
    check_config(program(tiny.CONF), dict(
        tiny.MODEL, rope=True, moe_shared_d_ff=0, moe_shared_gate="sigmoid",
        moe_router_experts=8, n_groups=2, embedding_multiplier=1.0,
        attention_multiplier=0.25, residual_multiplier=1.0,
        logits_scaling=1.0))


def test_a_shared_expert_the_file_does_not_state_is_refused():
    msg = refusal(program(tiny.CONF, moe_shared_dff=48), tiny.MODEL)
    assert "moe_shared_d_ff: file 0, program 48" in msg


def test_a_field_no_key_covers_is_refused():
    msg = refusal(program(tiny.CONF, window=16), tiny.MODEL)
    assert "window: program 16" in msg


# each key with a value that the variant's program does not have
DISAGREE = {"rope": True, "moe_shared_d_ff": 32, "moe_shared_gate": None,
            "moe_router_experts": 72, "n_groups": 4,
            "embedding_multiplier": 12.0, "attention_multiplier": 1 / 128,
            "residual_multiplier": 0.22, "logits_scaling": 16.0}


@pytest.mark.parametrize("key", sorted(DISAGREE))
def test_a_key_the_program_disagrees_on_is_refused(key):
    model = dict(tiny.VARIANT_MODEL, **{key: DISAGREE[key]})
    msg = refusal(program(tiny.VARIANT_CONF), model)
    assert msg.count(f"{key}: file ") == 1
    assert msg.count("file ") == 1


def test_every_key_that_disagrees_is_named():
    model = dict(tiny.VARIANT_MODEL, **DISAGREE)
    msg = refusal(program(tiny.VARIANT_CONF), model)
    for key in DISAGREE:
        assert f"{key}: file " in msg


def test_a_program_with_the_multipliers_passes_only_when_they_agree():
    """A program that states Granite's multipliers, the router's width and
    an ungated shared expert (fields the program does not have yet) is
    held to the file's keys."""
    from repro.configs.base import ModelConfig
    extra = {"embedding_multiplier": 12.0, "attention_multiplier": 1 / 128,
             "residual_multiplier": 0.22, "logits_scaling": 16.0,
             "moe_router_experts": 16, "moe_shared_gate": None}
    Granite = dataclasses.make_dataclass(
        "Granite", [(k, object, v) for k, v in extra.items()],
        bases=(ModelConfig,), frozen=True)
    base = program(tiny.VARIANT_CONF)
    cfg = Granite(**{f.name: getattr(base, f.name)
                     for f in dataclasses.fields(ModelConfig)}, **extra)
    model = dict(tiny.VARIANT_MODEL, **extra)
    check_config(cfg, model)
    msg = refusal(cfg, tiny.VARIANT_MODEL)
    for key in extra:
        assert f"{key}: file " in msg


def test_a_key_the_file_must_state_is_named_when_missing():
    model = {k: v for k, v in tiny.MODEL.items() if k != "rope_theta"}
    assert "rope_theta: not in the file" in refusal(program(tiny.CONF), model)
