#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration file under
``bench/configs/`` and a traffic file under ``bench/traffic/``; the
configuration file names the module that builds its weights
(``weights.module``) and its plain reference (``reference``), so a new
model family enters by new files.  The run makes the weights from the
seed on the device, builds the launcher's engine and continuous scheduler
(``repro.launch.serve.make_scheduler``) with the Pallas kernels, warms up
every shape the traffic reaches (``bench/warmup.py``), pre-rolls the
traffic into its steady state, and then measures for ``--seconds`` while
``bench/driver.py`` sends the requests through ``submit()``/``step()``.
Afterwards the served tokens of a sample of finished requests are compared
with the configuration's plain float32 reference (``bench/check.py``).

Earlier lines on stderr say how set-up went, how late the generator ran
and how many programs compiled inside the window; the last lines on stderr
are the numbers compared, each beside its limit.  The last line on stdout
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
with ``--trace 1``), ``device``, ``breakdown`` (traced runs) and
``checks``.  Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import ModuleType  # noqa: E402
from typing import Dict, List, Optional, Sequence  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

RUNS_DIR = ROOT / ".bench_runs"    # traces while they are read
if "TPU_LOG_DIR" not in os.environ:  # else libtpu logs under /tmp
    os.environ["TPU_LOG_DIR"] = str(RUNS_DIR / "tpu_logs")
    (RUNS_DIR / "tpu_logs").mkdir(parents=True, exist_ok=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file
    traffic: dict         # the traffic file
    limits: dict          # bench/limits/<cell>.json (number -> limit)
    end_to_end: List[dict]
    per_layer: List[dict]
    reference: ModuleType  # the module the configuration's "reference" names
    root: Path = ROOT     # the checkout the cell's files are read from


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _for_cell(metrics: Sequence[dict], cell: str) -> List[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files.  Its
    configuration file has to name its weights builder
    (``weights.module``) and its reference (``reference``): both are
    loaded here, and there is no default family."""
    from bench import plug

    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(work)}")
    w = work[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    for key, value in (("weights.module",
                        config.get("weights", {}).get("module")),
                       ("reference", config.get("reference"))):
        if value is None:
            raise SystemExit(f"{entry['file']}: no {key!r}; a configuration "
                             f"names its weights module and its reference")
    plug.load(config["weights"]["module"], root)
    limits_file = root / "bench" / "limits" / f"{name}.json"
    return Cell(
        name=name, chips=w["chips"], config=config,
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=(json.loads(limits_file.read_text())
                if limits_file.exists() else {}),
        end_to_end=_for_cell(bench["end_to_end"], name),
        per_layer=_for_cell(bench["per_layer"], name),
        reference=plug.load(config["reference"], root), root=root)


def configure_jax_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``; every program is cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def require_chips(n: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        log(f"bench: needs {n} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)")
        raise SystemExit(3)
    return devs[:n]


class CompileCounter:
    """Counts the XLA compilations JAX requests (persistent-cache hits
    included) and the seconds spent tracing, lowering and compiling,
    through JAX's monitoring events."""
    EVENT = "/jax/core/compile/backend_compile_duration"
    PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              EVENT: "compile"}

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0
        self.hits = 0
        self.seconds = {v: 0.0 for v in self.PHASES.values()}
        mon.register_event_duration_secs_listener(self._on)
        mon.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1
        if event in self.PHASES:
            self.seconds[self.PHASES[event]] += duration

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def describe(self) -> str:
        return (f"{self.n} compiles ({self.hits} from the persistent cache); "
                + ", ".join(f"{k} {v:.1f} s" for k, v in self.seconds.items()))


def model_config(conf: dict):
    """The program's ``ModelConfig`` of a configuration file: its
    ``model`` object, with the nested ``mla``, ``moe`` and ``ssm`` objects
    as the program's dimension classes."""
    from repro.configs.base import ModelConfig
    from repro.models.mla import MLADims
    from repro.models.moe import MoEDims
    from repro.models.ssm import SSMDims

    kw = dict(conf["model"])
    for key, dims in (("mla", MLADims), ("moe", MoEDims), ("ssm", SSMDims)):
        if isinstance(kw.get(key), dict):
            kw[key] = dims(**kw[key])
    return ModelConfig(name=conf["name"], **kw)


def engine_policy(traffic: dict):
    from repro.core.policy import get_policy

    pol = get_policy(traffic["engine_policy"]["base"])
    over = traffic["engine_policy"].get("overlay")
    return pol.overlay(over) if over else pol


def percentile(values: Sequence[float], q: float) -> float:
    """numpy's default (linear) percentile."""
    a = sorted(values)
    if not a:
        return math.nan
    pos = (len(a) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    return float(a[lo] + (a[hi] - a[lo]) * (pos - lo))


def ttft_ms(drv) -> List[float]:
    """First-token time - due time of every request due in the window, in
    ms.  A request with no first token when the run stopped waited at least
    until then (it is also counted as failed)."""
    return [((t.first if not math.isnan(t.first) else drv.t_stop) - t.due)
            * 1e3 for t in drv.due_in_window()]


def end_to_end(drv, seconds: float, setup_s: float) -> Dict[str, float]:
    w0, w1 = drv.t_win0, drv.t_end
    toks = sum(1 for t in drv.tracks for x in t.times if w0 <= x <= w1)
    ttft = ttft_ms(drv)
    itl = [(b - a) * 1e3 for t in drv.tracks
           for a, b in zip(t.times, t.times[1:]) if w0 <= a and b <= w1]
    return {"out_tok_per_s": toks / seconds,
            "ttft_p95_ms": percentile(ttft, 95),
            "itl_p95_ms": percentile(itl, 95),
            "setup_s": setup_s}


def _load_reader(name: str):
    base = name.split(".", 1)[0]
    path = BENCH / "metrics" / f"{base}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{base}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def kernel_families() -> Dict[str, dict]:
    fams = {}
    for p in sorted((BENCH / "kernels").glob("*.json")):
        d = json.loads(p.read_text())
        fams[d["family"]] = d
    return fams


@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader reads.  A metric ``<reader>.<phase>`` reads
    the ticks of that phase only (``StepRec.phase``)."""
    model: dict
    peak: Dict[str, float]
    window_steps: list               # StepRec of the measured window
    reference: ModuleType            # the cell's reference (model FLOPs)
    ttft: List[float] = dataclasses.field(default_factory=list)  # ttft_ms
    trace: object = None             # bench.trace.Trace of a traced run
    periods: Optional[list] = None   # (StepRec, lo_ns, hi_ns), traced ticks
    kernels: Dict[str, dict] = dataclasses.field(default_factory=dict)

    def steps(self, phase: str) -> list:
        return [s for s in self.window_steps if s.phase == phase]

    def traced(self, phase: str) -> list:
        return [p for p in (self.periods or []) if p[0].phase == phase]

    def device(self, phase: str):
        """Device time inside the traced ticks of one phase, or None."""
        from bench import trace as trace_lib

        spans = [(lo, hi) for _, lo, hi in self.traced(phase)]
        if self.trace is None or not spans:
            return None
        return trace_lib.over_periods(
            self.trace, spans,
            {f: d["patterns"] for f, d in self.kernels.items()})


def per_layer(cell: Cell, ctx: ReadContext) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        phase = m["name"].split(".", 1)[1] if "." in m["name"] else ""
        v = _load_reader(m["name"])(ctx, phase)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


class Tracer:
    """A profiler trace of the first ``trace_s`` seconds of the window."""

    def __init__(self, cell: str, trace_s: float):
        import jax

        self.jax = jax
        self.dir = RUNS_DIR / f"trace-{cell}"
        self.trace_s = trace_s
        self.t0 = self.t1 = math.nan
        self._ann = None

    def start(self):
        jax = self.jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.traced")
        self._ann.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        if self._ann is None:
            return
        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self._ann = None
        self.jax.profiler.stop_trace()

    def read(self, families: Dict[str, List[str]], keep: Optional[Path]):
        from bench import trace as trace_lib

        files = sorted(self.dir.rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        tr = trace_lib.from_xplane(str(files[-1]))
        if keep is not None:
            keep.mkdir(parents=True, exist_ok=True)
            shutil.copy(files[-1], keep / files[-1].name)
        shutil.rmtree(self.dir, ignore_errors=True)
        return tr, trace_lib.summarize(tr, families)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             backend: str = "pallas", t_start: float = T_START,
             controls: Sequence[str] = (),
             keep_trace: Optional[Path] = None) -> dict:
    """One run; returns the result line as a dict (``controls`` adds the
    lower-precision readings under ``"controls"``)."""
    import jax

    import repro.mp as mp
    from repro.launch.serve import make_scheduler

    from bench import check, roofline, traffic as traffic_lib, warmup, weights
    from bench.driver import Driver

    spec = cell.traffic
    model = cell.config["model"]
    devs = jax.devices()[: cell.chips]
    peak = roofline.peaks(devs[0].device_kind) if backend == "pallas" \
        else roofline.PEAKS["TPU v5 lite"]
    mp.configure(backend=backend)
    compiles = CompileCounter()
    marks = {"imports": time.perf_counter()}

    params = weights.make_params(cell.config, seed, cell.root)
    marks["weights"] = time.perf_counter()
    cfg = model_config(cell.config)
    sched = make_scheduler(cfg, params, slots=spec["slots"],
                           max_seq=spec["max_seq"],
                           policy=engine_policy(spec),
                           block_size=spec["block_size"],
                           n_blocks=spec["n_blocks"])
    marks["engine"] = time.perf_counter()
    stream = traffic_lib.make_stream(spec, model["vocab"], seed,
                                     spec["preroll_s"] + seconds)
    log(f"bench: weights {marks['weights'] - marks['imports']:.1f} s, "
        f"engine {marks['engine'] - marks['weights']:.1f} s; "
        f"{compiles.describe()}")
    warm = warmup.warm(sched, spec, stream, log=log)
    marks["warmup"] = time.perf_counter()

    tracer = Tracer(cell.name, min(spec["trace_s"], seconds)) if trace \
        else None
    window_compiles = {}

    def at_start():
        window_compiles["start"] = compiles.n
        window_compiles["traces0"] = sched.engine.trace_events
        if tracer:
            tracer.start()

    def at_end():
        window_compiles["end"] = compiles.n
        window_compiles["traces1"] = sched.engine.trace_events

    mark_list = [(0.0, at_start), (seconds, at_end)]
    if tracer:
        mark_list.append((tracer.trace_s, tracer.stop))
    annotate = jax.profiler.TraceAnnotation if trace else None
    drv = Driver(sched, spec, stream, annotate=annotate)
    drv.run(spec["preroll_s"], seconds, mark_list)
    setup_s = drv.t_win0 - t_start

    stats = sched.stats()
    mem = [d.memory_stats() or {} for d in devs]
    peak_bytes = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    due = drv.due_in_window()
    failed = sum(1 for t in due if math.isnan(t.first))
    late = [t.sent - t.due for t in due]
    log(f"bench: set-up {setup_s:.3f} s = imports "
        f"{marks['imports'] - t_start:.3f} + weights "
        f"{marks['weights'] - marks['imports']:.3f} + engine "
        f"{marks['engine'] - marks['weights']:.3f} + warm-up "
        f"{marks['warmup'] - marks['engine']:.3f} + pre-roll "
        f"{drv.t_win0 - marks['warmup']:.3f}")
    log(f"bench: warm-up {json.dumps(warm)}; programs traced in all "
        f"{stats['trace_events']}; {compiles.describe()}")
    log(f"bench: compiles inside the window "
        f"{window_compiles['end'] - window_compiles['start']}, traces "
        f"inside the window "
        f"{window_compiles['traces1'] - window_compiles['traces0']}")
    log(f"bench: generator lateness p95 "
        f"{percentile(late, 95) * 1e3 if late else 0.0:.3f} ms over "
        f"{len(late)} requests due in the window; queued at the close "
        f"{sched.n_queued}, active {sched.n_active}")

    result: Dict[str, object] = {
        "correct": False, "attempted": len(due), "failed": failed}
    if trace:
        from bench import trace as trace_lib

        tr, summary = tracer.read(
            {f: d["patterns"] for f, d in kernel_families().items()},
            keep_trace)
        traced = [s for s in drv.steps
                  if s.t0 >= tracer.t0 and s.t1 <= tracer.t1]
        try:
            periods = trace_lib.step_periods(tr, traced)
        except ValueError as e:     # the trace readers then read nothing
            log(f"bench: ticks not found in the trace: {e}")
            periods = None
        ctx = ReadContext(model=model, peak=peak,
                          window_steps=drv.window_steps(),
                          reference=cell.reference,
                          ttft=ttft_ms(drv), trace=tr,
                          periods=periods, kernels=kernel_families())
        result["metrics"] = per_layer(cell, ctx)
        log(f"bench: idle by host activity {json.dumps(summary.idle_by_host)}")
    else:
        e2e = end_to_end(drv, seconds, setup_s)
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.device_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    result["device"] = device
    result["window"] = {
        "compiles": window_compiles["end"] - window_compiles["start"],
        "traces": window_compiles["traces1"] - window_compiles["traces0"],
        "programs": stats["trace_events"],
        "lateness_p95_ms": percentile(late, 95) * 1e3 if late else 0.0}

    # the program's state goes before the reference runs
    finished = [t for t in drv.tracks
                if t.sreq.state == "done" and t.done >= drv.t_win0]
    drv.sched = None
    del sched, drv
    gc.collect()
    t_chk = time.perf_counter()
    picked = check.sample(finished, seed, spec["check"])
    numbers = check.widest_gaps(cell.reference, params, model, picked,
                                ("highest",) + tuple(controls))
    numbers["wrong_length"] = float(check.wrong_length(finished))
    program = {k: v for k, v in numbers.items() if "@" not in k}
    ok, checks = check.verdict(program, cell.limits)
    result["correct"] = bool(ok and failed == 0)
    if controls:
        result["controls"] = {k: v for k, v in numbers.items() if "@" in k}
    log(f"bench: reference over {len(picked)} requests, "
        f"{sum(len(t.sreq.out) for t in picked)} served tokens, "
        f"{time.perf_counter() - t_chk:.3f} s")
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="copy the profiler trace into this directory")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    configure_jax_cache()
    require_chips(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
