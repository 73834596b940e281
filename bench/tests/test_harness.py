"""A whole run of the cell at smoke size on the CPU (the look for a chip
skipped, kernels in the interpreter): the warmed window compiles nothing,
the served tokens pass the comparison, and the lower-precision control and
each fault the cell can have, planted in the timed path, do not."""
import dataclasses
import json
import time

import numpy as np
import pytest

from conftest import smoke_conf, smoke_traffic

import bench.run as R
from bench import check

CELL = "glm3l-m23-closed"
# limit for the smoke size, set by the rule the cell's limit follows
# (above the program's widest gap, below the control's): at 64 wide the
# program's M23 reads under 1e-6 and the control at `high` several 1e-6
SMOKE_LIMITS = {"gap_M23": 2e-6, "wrong_length": 0.0}


def smoke_cell(name: str = CELL, **traffic) -> R.Cell:
    cell = R.load_cell(name)
    return dataclasses.replace(cell, config=smoke_conf(cell.config["name"]),
                               traffic=smoke_traffic(cell.traffic, **traffic),
                               limits=SMOKE_LIMITS)


def run(cell, seed, seconds=3.0, **kw):
    return R.run_cell(cell, seed, seconds, False, backend="pallas_interpret",
                      t_start=time.perf_counter(), **kw)


@pytest.fixture(scope="module")
def closed_run():
    return run(smoke_cell(), 2**33 + 11, controls=("high",))


def test_result_line_has_the_contract_keys(closed_run):
    line = json.loads(json.dumps(closed_run))
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"out_tok_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in line["metrics"].values())


def test_warmed_window_compiles_nothing(closed_run):
    assert closed_run["window"]["traces"] == 0
    assert closed_run["window"]["compiles"] == 0


def test_served_tokens_pass(closed_run):
    assert closed_run["correct"], closed_run["checks"]


def test_control_fails(closed_run):
    """The reference in the program's place one precision below M23
    (`high`, three bf16 passes) fails the comparison."""
    control = {"gap_M23": closed_run["controls"]["gap_M23@high"],
               "wrong_length": 0.0}
    ok, _ = check.verdict(control, SMOKE_LIMITS)
    assert not ok, control


def test_open_loop_window_compiles_nothing():
    """The open loop (the generator's other mode, for later cells) warms
    every batch width it can reach."""
    cell = smoke_cell(loop="open", rate_per_s=6.0)
    res = run(cell, 2**33 + 13, seconds=2.0)
    assert res["window"]["traces"] == 0, res["window"]
    assert res["attempted"] > 0


def _plant(monkeypatch, fault):
    from repro.serve import primitives as prim
    from repro.serve.kv_cache import PagedKVPool

    if fault == "state_unchanged":
        # every step's K/V writes are dropped: the pool never changes
        monkeypatch.setattr(PagedKVPool, "update", lambda self, k, v: None)
    elif fault == "token_altered_prefill":
        real = prim.prefill_request
        monkeypatch.setattr(prim, "prefill_request",
                            lambda *a, **k: (real(*a, **k) + 1) % 256)
    else:
        for name in ("decode_bucket_step", "decode_mixed_step"):
            real = getattr(prim, name)

            def broken(*a, _real=real, **k):
                toks, ok = _real(*a, **k)
                toks = np.asarray(toks).copy()
                if fault == "token_altered_decode":
                    toks = (toks + 1) % 256
                else:   # half of the batch left out: its rows get the
                    h = (len(toks) + 1) // 2    # first half's tokens
                    toks[h:] = toks[: len(toks) - h]
                return toks, ok

            monkeypatch.setattr(prim, name, broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered_decode",
                                   "token_altered_prefill"])
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    _plant(monkeypatch, fault)
    res = run(smoke_cell(), 2**33 + 12, seconds=2.0)
    assert not res["correct"]
    assert res["checks"]["gap_M23"]["value"] > SMOKE_LIMITS["gap_M23"]
