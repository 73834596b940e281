"""The serving tick's host spans and program names, read back from a
profiler trace of a smoke scheduler: every decode launch opens
``serve.decode`` inside the tick's ``serve.tick`` with its ``prep``,
``launch``, ``sync`` and ``finish`` children in that order, on the
per-policy and on the mixed-lane step alike; each prefill sits inside the
tick's ``serve.admit``; and the jitted steps are named after their
factories (``jit_paged_decode``, not ``jit_wrapped``)."""
import numpy as np
import pytest
import jax

from repro.configs.registry import get_config
from repro.core.policy import PrecisionPolicy
from repro.models import transformer as T
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import ContinuousScheduler, ScheduledRequest

CFG = get_config("paper-mpfp-100m", smoke=True)
DECODE_CHILDREN = ["serve.decode.prep", "serve.decode.launch",
                   "serve.decode.sync", "serve.decode.finish"]


def _requests(rids, modes=None, size=9, max_new=4):
    rng = np.random.default_rng(7)
    modes = modes or [None] * len(rids)
    return [ScheduledRequest(
        rid=r, prompt=rng.integers(0, CFG.vocab, size=size).astype(np.int32),
        max_new=max_new, mode=m) for r, m in zip(rids, modes)]


def _traced_ticks(params, out, modes=None, n_ticks=3):
    """``n_ticks`` ticks under the profiler: the first admits two requests
    (prefills) and decodes them, the others only decode, the last
    finishing both.  Every step is compiled before the trace starts.
    Returns the ``serve.*`` spans in start order and the program names."""
    from jax.profiler import ProfileData

    eng = ServeEngine(CFG, params, max_batch=4, max_seq=64,
                      policy=PrecisionPolicy.serve_default())
    sched = ContinuousScheduler(eng, n_blocks=32, block_size=8)
    for r in _requests([90, 91], modes, max_new=6):   # warm every shape
        sched.submit(r)
    sched.run()
    for r in _requests([11, 12], modes):
        sched.submit(r)
    with jax.profiler.trace(str(out)):
        for _ in range(n_ticks):
            sched.step()
    path = sorted(out.rglob("*.xplane.pb"))[-1]
    spans, modules = [], set()
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith("serve."):
                    spans.append((name, e.start_ns,
                                  e.start_ns + e.duration_ns))
                module = dict(e.stats).get("hlo_module")
                if isinstance(module, str):
                    modules.add(module)
    return sorted(spans, key=lambda s: s[1]), modules


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    return _traced_ticks(params, tmp_path_factory.mktemp("trace"))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _assert_decode_nesting(spans, n_ticks):
    ticks = [s for s in spans if s[0] == "serve.tick"]
    decodes = [s for s in spans if s[0] == "serve.decode"]
    assert len(ticks) == n_ticks and len(decodes) == n_ticks
    for d in decodes:
        assert any(_inside(d, t) for t in ticks)
        kids = [s for s in spans if s[0].startswith("serve.decode.")
                and _inside(s, d)]
        assert [k[0] for k in kids] == DECODE_CHILDREN
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    emits = [s for s in spans if s[0] == "serve.emit"]
    assert len(emits) == n_ticks
    assert all(d[2] <= e[1] for d, e in zip(decodes, emits))


def test_each_decode_lies_inside_a_tick_with_its_children_in_order(traced):
    _assert_decode_nesting(traced[0], 3)


def test_each_prefill_sits_inside_the_admission_with_its_children(traced):
    spans, _ = traced
    admits = [s for s in spans if s[0] == "serve.admit"]
    prefills = [s for s in spans if s[0] == "serve.prefill"]
    # both prefills sit in the first tick's admission, the only one that
    # admitted anything; every tick opens one serve.admit
    assert len(admits) == 3 and len(prefills) == 2
    assert all(_inside(p, admits[0]) for p in prefills)
    for p in prefills:
        kids = [s[0] for s in spans if s[0].startswith("serve.prefill.")
                and _inside(s, p)]
        assert kids == ["serve.prefill.launch", "serve.prefill.sync"]


def test_jitted_steps_are_named_after_their_factories(traced):
    _, modules = traced
    assert {"jit_paged_decode", "jit_paged_prefill"} <= modules
    assert "jit_wrapped" not in modules


def test_mixed_lane_decode_opens_the_same_spans(params, tmp_path):
    # two formats in one batch take the one-launch mixed step
    spans, modules = _traced_ticks(params, tmp_path, modes=["M16", "M23"])
    _assert_decode_nesting(spans, 3)
    assert "jit_mixed_decode" in modules
    assert "jit_paged_decode" not in modules
