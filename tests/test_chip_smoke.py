"""``chip_smoke.py``'s phases on the CPU at a tiny size.

The script's ``main`` refuses to run without a TPU; its phases are
plain functions, driven here on a Gowalla x0.05 graph so a wrong path,
argument or check fails before a chip run does.  On the CPU the engines
serve through the fused XLA program (bit-identical to the megakernel,
see ``test_fused``), so the megakernel-mode assertion is checked on its
refusal only.
"""

import importlib.util
import os

import numpy as np
import pytest

from repro.data import workload

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def built(smoke):
    g, index = smoke.build_host(0.05)
    us, rects = workload(g, n_queries=64, seed=1)
    return g, index, us, rects


def test_main_refuses_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert "no TPU found" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_serve_and_check_phases(smoke, built):
    g, index, us, rects = built
    reach, eng = smoke.serve_reach(index, us, rects, n_shards=1, batch=32)
    assert eng.n_shards == 1 and len(reach) == len(us)
    counts, col, qeng = smoke.serve_analytics(index, us[:32], rects[:32],
                                              k=4)
    smoke.check_answers(g, index, us, rects, reach, counts, col, k=4,
                        n_oracle=8)
    with pytest.raises(AssertionError):
        smoke.require_megakernel(qeng)       # fused XLA on the CPU


def test_check_answers_catches_a_wrong_answer(smoke, built):
    g, index, us, rects = built
    reach, _ = smoke.serve_reach(index, us, rects, n_shards=1, batch=32)
    counts, col, _ = smoke.serve_analytics(index, us[:32], rects[:32], k=4)
    bad = reach.copy()
    bad[0] = ~bad[0]
    with pytest.raises(AssertionError):
        smoke.check_answers(g, index, us, rects, bad, counts, col, k=4,
                            n_oracle=8)


def test_device_build_phase(smoke):
    dev = smoke.device_build(0.05, n_queries=32)
    assert dev.backend == "device" and dev.forest.device is not None


def test_sharded_vs_single_phase(smoke, built):
    _, index, us, rects = built
    eng = smoke.sharded_vs_single(index, us, rects, n_shards=1, batch=32)
    assert len(eng.arena_devices()) == 1
    assert np.all(eng.shard_queries >= 0)
