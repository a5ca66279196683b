"""One cell of the benchmark, run once, as ``bench/run.py`` drives it.

Everything that belongs to one configuration, traffic mix or metric is
found by name from ``BENCHMARK.json``:

* a configuration is a directory under ``configs/`` holding its manifest
  (the file ``BENCHMARK.json`` names), ``generate.py`` (fields from the
  seed) and ``reference.py`` (the plain NumPy QoIs);
* a traffic mix is ``traffic/<name>.json``, read by ``bench.traffic``;
* a metric is ``metrics/<name>.py``, whose ``read(readings)`` returns a
  number or None when there is nothing to read.

A run: check the devices, point the compile cache at the checkout, make
the fields from the seed, refactor them into a fresh archive under
``.bench_scratch/`` (deleted on exit), warm up on a server of its own over
that archive, then open a fresh server over it and run the closed-loop
window for ``seconds``.  Clients stop sending at the window's end and
their last answers are awaited.  The result is one JSON line.
"""
from __future__ import annotations

import importlib.util
import json
import logging
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import TimeoutError as FutureTimeout
from contextlib import nullcontext
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

from bench import check, traffic

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
SCRATCH = ROOT / ".bench_scratch"
LATE_S = 60.0           # how long past the window an answer is awaited
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class BenchError(RuntimeError):
    """The run cannot be made: no result line is printed."""


# -- finding things by name ------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    manifest: dict
    config_dir: Path
    mix: dict
    metrics: List[dict]          # end-to-end first, then per-layer
    root: Path

    def module(self, stem: str):
        return load_module(self.config_dir / f"{stem}.py")


def load_module(path: Path):
    """Import the Python file at ``path`` under a name of its own."""
    name = "bench_file_" + "_".join(path.with_suffix("").parts[-3:]) \
        .replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise BenchError(f"cannot import {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric with a ``workloads`` list applies to those cells; one
    without, end-to-end, to every cell, and per-layer, to every cell that
    reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its configuration
    manifest, traffic mix and the metrics it reports."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_file = root / configs[w["config"]]["file"]
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, reported)]
    return Cell(name=name, chips=int(w["chips"]),
                manifest=json.loads(cfg_file.read_text()),
                config_dir=cfg_file.parent,
                mix=traffic.load(root / "bench" / "traffic"
                                 / f"{w['traffic']}.json"),
                metrics=e2e + per_layer, root=root)


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    return load_module(root / "bench" / "metrics" / f"{name}.py").read


def load_peaks(kind: str, path: Path = BENCH / "peaks.json") -> dict:
    """The published peaks of one device kind; an unknown kind is an
    error, never a default."""
    table = json.loads(path.read_text())["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in {path}")
    return table[kind]


def check_devices(chips: int) -> dict:
    """The devices JAX found, refused unless they are TPUs with a known
    peak and at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    load_peaks(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at the checkout's fixed path
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program
    however quickly it compiled, so that a run after the first in a
    checkout compiles nothing."""
    import jax
    from repro.compile_cache import use_checkout_cache
    path = use_checkout_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# -- what the metric readers get -------------------------------------------

@dataclass
class Readings:
    setup_s: float
    answers: List[check.Answer]
    counters: Dict[str, float]           # RetrievalServer.metrics()
    trace: Optional[object] = None       # trace_reduce.TraceSummary

    @property
    def certified(self) -> List[check.Answer]:
        return [a for a in self.answers if a.certified]

    def span_ms_per_answer(self, span: str) -> Optional[float]:
        """Milliseconds of the program span ``span``'s self time in the
        traced window, summed over threads, per answer; None with no
        trace, no answer or no such span in the trace."""
        if self.trace is None or not self.answers:
            return None
        s = self.trace.span_self_s.get(span)
        return None if s is None else 1e3 * s / len(self.answers)

    def per_client(self) -> Dict[int, List[check.Answer]]:
        out: Dict[int, List[check.Answer]] = {}
        for a in self.answers:
            out.setdefault(a.client, []).append(a)
        return out


# -- compile accounting ----------------------------------------------------

class CompileNames(logging.Handler):
    """Names of the programs JAX compiles while installed (from its
    ``jax_log_compiles`` records, kept off the console)."""

    def __init__(self):
        super().__init__()
        self.names: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        if str(record.msg).startswith("Compiling") and record.args:
            self.names.append(str(record.args[0]))

    def __enter__(self):
        import jax
        self._logger = logging.getLogger("jax")
        self._propagate = self._logger.propagate
        self._logger.addHandler(self)
        self._logger.propagate = False
        jax.config.update("jax_log_compiles", True)
        return self

    def __exit__(self, *exc):
        import jax
        jax.config.update("jax_log_compiles", False)
        self._logger.removeHandler(self)
        self._logger.propagate = self._propagate


class CompileCounter:
    """Counts JAX's trace / lower / backend-compile events while on."""

    def __init__(self):
        self._mu = threading.Lock()
        self.events: Dict[str, int] = {}
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            with self._mu:
                key = event.rsplit("/", 1)[-1]
                self.events[key] = self.events.get(key, 0) + 1
                self.seconds += duration

    def snapshot(self) -> Dict[str, int]:
        with self._mu:
            return dict(self.events)


# -- the run ---------------------------------------------------------------

def _server(cell: Cell, fields, store: str, plant):
    from repro.launch.serve import RetrievalServer
    kwargs = dict(cell.manifest.get("server", {}))
    kwargs.setdefault("workers", int(cell.mix["clients"]))
    server = RetrievalServer(fields, method=cell.manifest.get("method", "hb"),
                             store_path=store, **kwargs)
    if plant is not None:
        plant(server)
    return server


def _retire(server, name: str) -> None:
    """Close a client's finished session, as a server retires an idle
    one: its pooled contribution leases and device state go back."""
    session = server.sessions.pop(name, None)
    if session is not None:
        session.close()


_BATCH_CALLS = ("submit_decode", "submit_recompose")


def _warm_up(server, cell: Cell, log: Callable[[str], None]) -> None:
    """The cell's own request shapes, at its own concurrency: each client
    asks every QoI (each client starting at another one) first in a fresh
    session at the tau where the client starts its first session in the
    window, then down the whole tau ladder in another.  The clients run
    together, so the decode batcher forms batches as in the window; which
    items meet in one batch depends on timing, so ``_warm_batches`` then
    compiles every batch size the clients can form."""
    from repro.launch.serve import Request
    taus = [float(t) for t in cell.mix["taus"]]
    qois = list(cell.manifest["qois"])
    clients = int(cell.mix["clients"])
    recorded = _record_batches(server.decode_batcher) if clients > 1 else None

    def client(c: int) -> None:
        start = traffic.first_tau(cell.mix, c)
        for i in range(len(qois)):
            q = qois[(i + c) % len(qois)]
            for k, steps in enumerate((taus[start:], taus)):
                if k == 1 and start == 0:
                    break                 # the first session was the ladder
                name = f"warm.c{c}.{q}.{k}"
                for tau in steps:
                    try:
                        server.submit(Request(client=name, qois=[q],
                                              tau=tau)).result()
                    except Exception as e:   # the window's check counts it
                        log(f"[bench] warm-up {q} tau={tau} failed: {e!r}")
                        break
                _retire(server, name)
    _run_threads([lambda c=c: client(c) for c in range(clients)])
    if recorded is not None:
        _warm_batches(server.decode_batcher, recorded, clients, log)


def _record_batches(batcher) -> Optional[Dict[object, tuple]]:
    """Record the first call of each item shape (a ticket's ``key``) that
    the decode batcher is given, or None where there is no batcher or it
    has none of ``_BATCH_CALLS``."""
    calls = [k for k in _BATCH_CALLS if callable(getattr(batcher, k, None))]
    if batcher is None or not calls:
        return None
    recorded: Dict[object, tuple] = {}
    for kind in calls:
        original = getattr(batcher, kind)

        def record(*args, _original=original, _kind=kind, **kwargs):
            ticket = _original(*args, **kwargs)
            key = getattr(ticket, "key", None)
            if key is not None:
                recorded.setdefault(key, (_kind, args, kwargs))
            return ticket
        setattr(batcher, kind, record)      # on this instance only
    return recorded


def _warm_batches(batcher, recorded: Dict[object, tuple], clients: int,
                  log: Callable[[str], None]) -> None:
    """Give the batcher 2, 4, ... up to ``clients`` (rounded up to a power
    of two) copies of each recorded item at once, so that every vmapped
    program the window's clients can form is compiled.  Without it a
    pairing that the warm-up never met compiled inside the window in 4 of
    16 isabel.ladder runs on one TPU v5e (PERF.md).  Where the batcher no longer takes these calls, it
    is skipped and said so."""
    import jax
    for kind in _BATCH_CALLS:
        vars(batcher).pop(kind, None)         # back to the class's methods
    widest = 1 << (clients - 1).bit_length()
    try:
        for key, (kind, args, kwargs) in sorted(recorded.items(), key=str):
            b = 2
            while b <= widest:
                tickets = [getattr(batcher, kind)(*args, **kwargs)
                           for _ in range(b)]
                batcher.flush()
                for t in tickets:
                    jax.block_until_ready(t.result())
                b <<= 1
    except Exception as e:        # the program's batcher changed its calls
        log(f"[bench] batch warm-up skipped: {e!r}")


def _run_threads(bodies: Sequence[Callable[[], None]]) -> None:
    """Run each body on a thread of its own; re-raise the first error."""
    errors: List[BaseException] = []

    def guard(body):
        try:
            body()
        except BaseException as e:          # reported after the join
            errors.append(e)
    threads = [threading.Thread(target=guard, args=(b,), daemon=True)
               for b in bodies]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def _window(server, cell: Cell, seconds: float,
            sampler: check.Sampler, variables, annotate) -> List[check.Answer]:
    """The closed loop: each client sends its next request when its last
    answer is in, until ``seconds`` have passed; then the last answers are
    awaited (up to ``LATE_S`` more)."""
    from repro.launch.serve import Request
    from repro.serve import ServerOverloadedError
    scripts = traffic.client_sessions(cell.mix, cell.manifest["qois"])
    answers: List[check.Answer] = []
    mu = threading.Lock()
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def client(c: int) -> None:
        for sess in scripts[c]:
            for tau in sess.taus:
                if time.perf_counter() >= deadline:
                    _retire(server, sess.name)
                    return
                a = check.Answer(client=c, session=sess.name, qoi=sess.qoi,
                                 tau=tau, submitted=0.0, done=0.0)
                with annotate("bench.submit"):
                    t_sub = time.perf_counter()
                    try:
                        fut = server.submit(Request(client=sess.name,
                                                    qois=[sess.qoi], tau=tau))
                    except ServerOverloadedError as e:
                        fut, a.error = None, f"shed: {e}"
                if fut is not None:
                    with annotate("bench.wait"):
                        try:
                            out = fut.result(timeout=max(
                                0.0, deadline + LATE_S - time.perf_counter()))
                            a.latency_s = float(out["latency_s"])
                            a.bytes_moved = int(out["bytes_moved"])
                            a.bound = float(out["est_errors"][sess.qoi])
                            a.guaranteed = bool(out["guaranteed"])
                            a.degraded = bool(out["degraded"])
                        except FutureTimeout:
                            a.error = f"no answer {LATE_S}s after the window"
                        except Exception as e:     # the request failed
                            a.error = f"{type(e).__name__}: {e}"
                a.submitted, a.done = t_sub - t0, time.perf_counter() - t0
                with mu:
                    answers.append(a)
                if a.error is not None:
                    if a.error.startswith("no answer"):
                        return
                    break                          # to a fresh session
                session = server.sessions.get(sess.name)
                sampler.offer(a, lambda: {v: session.current(v)[0]
                                          for v in variables[sess.qoi]})
            _retire(server, sess.name)
    with annotate("bench.window"):
        _run_threads([lambda c=c: client(c)
                      for c in range(int(cell.mix["clients"]))])
    return answers


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, plant: Optional[Callable] = None,
             warm_up: bool = True,
             log: Callable[[str], None] = lambda s: print(s, file=sys.stderr,
                                                          flush=True)) -> dict:
    """Run ``cell`` once and return its result line as a dict.
    ``plant(server)``, where given, alters each server before it serves:
    the control and the fault tests plant a broken guarantee there.
    ``warm_up=False`` skips the warm-up server, for later runs in a
    process whose programs an earlier run has compiled."""
    device = check_devices(cell.chips)
    import jax
    import jax.monitoring
    cache = use_compile_cache()
    log(f"[bench] {cell.name} seed={seed} on {device['kind']} "
        f"x{device['count']}; compile cache {cache}")
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    SCRATCH.mkdir(exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="run-", dir=SCRATCH)
    try:
        t = time.perf_counter()
        fields = cell.module("generate").generate(cell.manifest, seed)
        reference = cell.module("reference")
        variables = reference.VARIABLES
        log(f"[bench] generate {time.perf_counter() - t:.3f}s")
        store = str(Path(workdir) / "archive.prs")
        t = time.perf_counter()
        warm = _server(cell, fields, store, plant)
        log(f"[bench] refactor+save+open {warm.refactor_s:.3f}s "
            f"({warm.archive.total_nbytes} B)")
        try:
            if warm_up:
                _warm_up(warm, cell, log)
        finally:
            warm.close()
        server = _server(cell, fields, store, plant)
        log(f"[bench] warm-up {time.perf_counter() - t:.3f}s; compile "
            f"{compiles.seconds:.3f}s summed, {compiles.snapshot()}")
        strata = [(q, float(tau)) for q in cell.manifest["qois"]
                  for tau in cell.mix["taus"]]
        sampler = check.Sampler(seed, strata)
        before = compiles.snapshot()
        tracer = _Tracer(workdir) if trace else None
        annotate = _annotation if trace else (lambda name: nullcontext())
        try:
            if tracer is not None:
                tracer.start()
            setup_s = time.perf_counter() - t_start
            t_window = time.perf_counter()
            with CompileNames() as compiled:
                answers = _window(server, cell, seconds, sampler,
                                  variables, annotate)
            window_s = time.perf_counter() - t_window
        finally:
            if tracer is not None:
                tracer.stop()
        after = compiles.snapshot()
        in_window = {k: after.get(k, 0) - before.get(k, 0) for k in after}
        log(f"[bench] window {window_s:.3f}s, {len(answers)} requests; "
            f"compiles inside the window: {in_window} {compiled.names[:20]}")
        stats = jax.devices()[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        counters = server.metrics()
        server.close()
        summary = tracer.reduce() if tracer is not None else None
        t = time.perf_counter()
        checks = check.compare(sampler.samples(), answers, reference, fields,
                               cell.manifest["limits"])
        log(f"[bench] reference check {time.perf_counter() - t:.3f}s over "
            f"{len(sampler.samples())} sampled answers")
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
        shutil.rmtree(workdir, ignore_errors=True)
    readings = Readings(setup_s=setup_s, answers=answers, counters=counters,
                        trace=summary)
    return _result(cell, readings, device, checks, trace, log)


def _result(cell: Cell, readings: Readings, device: dict, checks: dict,
            trace: bool, log) -> dict:
    # untraced runs report the end-to-end metrics (those with a bound),
    # traced runs the per-layer ones
    wanted = [m for m in cell.metrics if ("bound" in m) != trace]
    metrics = {}
    for m in wanted:
        value = metric_reader(m["name"], cell.root)(readings)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": check.passed(checks),
           "attempted": len(readings.answers),
           "failed": sum(1 for a in readings.answers if not a.certified),
           "metrics": metrics, "device": device}
    if trace and readings.trace is not None:
        s = readings.trace
        device["busy_s"] = s.busy_s
        device["window_s"] = s.window_s
        ops = sorted(s.programs.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(s.idle_by_program_span.items(),
                      key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": [[k, v] for k, v in ops],
                            "idle_gaps": [[k, v] for k, v in gaps]}
        log(f"[bench] device programs (s): {dict(ops)}")
        log(f"[bench] idle by program span (s): {dict(gaps)}")
        log(f"[bench] program span self time (s): {s.span_self_s}")
    out["checks"] = checks
    return out


def _annotation(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


class _Tracer:
    """The profiler over the window; the program's own spans and the
    harness's ``bench.*`` annotations are host events in its trace."""

    def __init__(self, workdir: str):
        self.logdir = str(Path(workdir) / "trace")

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.logdir, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def reduce(self):
        from bench import trace_reduce
        return trace_reduce.reduce_trace(trace_reduce.find_xspace(self.logdir))


def emit(result: dict) -> None:
    """The numbers compared as the last lines of standard error, and the
    result as the last line of standard output."""
    for line in check.describe(result["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
