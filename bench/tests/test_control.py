"""The comparison that decides ``correct`` fails its control: the plain
reference computed in float8 (the next precision below what the
configuration states) put in the program's place, judged by the cell's
own limits.  At toy size on the CPU, for a MoE and a dense configuration;
the chip readings at each cell's own size are taken with
``bench/tools/readings.py``, which judges the control the same way."""

import time

import jax
import pytest

import tiny
from drivers import lm_serving


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 987654321])
@pytest.mark.parametrize("conf", [tiny.CONF, tiny.DENSE_CONF],
                         ids=["moe", "dense"])
def test_program_passes_and_the_control_fails(conf, seed, tmp_path):
    res = lm_serving.run(conf, tiny.MIX, seed, 2.0, False,
                         time.perf_counter(), str(tmp_path),
                         jax.devices()[:1], control=True)
    assert res.correct
    ctl = res.gaps["control_checks"]
    assert set(ctl) == set(res.checks)
    assert not lm_serving.passed(ctl)
    assert any(ctl[k]["value"] > lim for k, lim in conf["limits"].items())
