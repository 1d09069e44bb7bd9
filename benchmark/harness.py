"""The benchmark's harness: one cell, one process, one window.

Driven by data. ``BENCHMARK.json`` names cells and metrics; everything that
belongs to one configuration, one architecture, one traffic mix, one
per-layer metric or one cell's limits is a file of its own, found by name
under the manifest's ``paths`` (``<architecture>`` is the configuration
file's ``architecture`` key):

* ``<config.file>``                      the configuration as it is run
* ``<path>/traffic/<traffic>.json``      the mix's parameters (``traffic.py`` reads it)
* ``<path>/loops/<loop>.py``             the arrivals the mix's ``loop`` key names
* ``<path>/end_to_end/<name>.py``        one reader an end-to-end metric
* ``<path>/build/<architecture>.py``     the system under test: ``build(cfg, key,
  devices) -> (model, engine, server)``, the weights on the devices when it
  returns, and ``release(model, engine, server)``, which frees them
* ``<path>/counts/<architecture>.py``    operations and bytes: ``prefill(cfg, p_len)``,
  ``decode_steps(cfg, steps, row_lengths)`` and one function a kernel whose
  roofline a reader reports, each ``{"flops", "bytes"}`` (``run.counts``)
* ``<path>/reference/<architecture>.py`` the plain reference
* ``<path>/layer_metrics/<name>.py``     one reader a per-layer metric
* ``<path>/limits/<cell>.json``          the limits ``correct`` holds the cell to

So a new architecture is new files and entries alone. Of the configuration
the harness itself reads what every served model has: ``architecture``,
``vocab_size`` (the vocabulary held here: a sliced vocabulary is a smaller
one, and traffic, logits and sampling are over the slice), ``torch_dtype``
and ``serving.{chips, tp, mesh_axis, slots, max_len, chunk, block_size,
backend}``; every other key is the architecture's files' to read.

From the program the harness takes the system under test (an
``InferenceServer`` over an ``Engine`` over the architecture's model, as its
``build`` file makes them) and its counters. It drives ``submit`` and
``step`` in one thread and times tokens on its own clock in ``on_token``.

A run: set-up (import, weights on the device from the seed, engine, one
warm-up request of every prompt length of the mix), the measured window
(arrivals as the mix's ``loop`` says), with ``--trace 1`` a few traced
seconds of the same steady loop after the window has closed, the drain, the
memory reading, then the program's state is freed and the reference decides
``correct``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import sys
import tempfile
import time

import numpy as np

from benchmark import correct as correct_mod
from benchmark import counts, peaks, stats, traffic
from benchmark import trace as trace_mod

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: Counters of the program that a healthy run leaves at zero (printed on an
#: earlier line; a fallback behind a result is a different system).
ZERO_COUNTERS = (
    "tdt_engine_fallbacks_total",
    "tdt_serving_recoveries_total",
    "tdt_serving_recovery_retries_total",
    "tdt_serving_preemptions_total",
    "tdt_serving_restores_total",
    "tdt_resilience_watchdog_timeouts_total",
    "tdt_ep_dropped_tokens_total",
)

STEP_MARK = "server.step"


class Lowerings:
    """Counts jit cache misses: each traces and lowers a program, whether or
    not the persistent cache then spares the backend compile."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.n += 1


# ------------------------------------------------------------------ the data


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    limits: dict
    end_to_end: list  # (manifest entry, reader module) this cell reports
    per_layer: list  # (manifest entry, reader module)
    paths: list
    manifest: dict
    reference: object = None  # the architecture's plain-reference module
    build: object = None  # the architecture's build and release
    counts: object = None  # the architecture's counts beside the common arithmetic
    loop: object = None  # the module that makes the mix's arrivals


def _find(root: pathlib.Path, paths, rel: str) -> pathlib.Path:
    for p in paths:
        f = root / p / rel
        if f.is_file():
            return f
    raise FileNotFoundError(f"{rel} under none of {list(paths)}")


def _module(path: pathlib.Path):
    name = "benchfile_" + "".join(c if c.isalnum() else "_" for c in str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lists(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(manifest_path, workload: str, root=None) -> Cell:
    """The cell's data. ``root`` is the checkout the manifest's paths are
    relative to: the manifest's own directory unless a test says otherwise."""
    manifest_path = pathlib.Path(manifest_path)
    root = manifest_path.parent if root is None else pathlib.Path(root)
    manifest = json.loads(manifest_path.read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    paths = manifest["paths"]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = json.loads(_find(root, paths, f"traffic/{w['traffic']}.json").read_text())
    limits = json.loads(_find(root, paths, f"limits/{workload}.json").read_text())
    traffic.check(mix)
    e2e = [(m, _module(_find(root, paths, f"end_to_end/{m['name']}.py")))
           for m in manifest["end_to_end"] if _lists(m, workload)]
    reported = {m["name"] for m, _ in e2e}
    layer = []
    for m in manifest["per_layer"]:
        if _lists(m, workload) and m["moves"] in reported:
            layer.append((m, _module(_find(root, paths, f"layer_metrics/{m['name']}.py"))))
    cell = Cell(workload, int(w["chips"]), cfg, mix, limits, e2e, layer, paths, manifest)
    arch = cfg["architecture"]
    cell.reference = _module(_find(root, paths, f"reference/{arch}.py"))
    cell.build = _module(_find(root, paths, f"build/{arch}.py"))
    cell.counts = counts.Counts(_module(_find(root, paths, f"counts/{arch}.py")))
    cell.loop = _module(_find(root, paths, f"loops/{mix['loop']}.py"))
    return cell


def tpu_devices(manifest_path, workload: str):
    """The TPU devices a command runs ``workload`` on, or None (with a line
    on stderr) where jax reports another platform or fewer chips than the
    cell asks for: nothing here falls back to another device."""
    import jax

    chips = load_cell(manifest_path, workload).chips
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark: {workload} needs {chips} TPU device(s), jax reports "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return None
    enable_compile_cache()
    return devices


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache where the program keeps it (it
    honours ``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``),
    for every program however small or quick to compile: by default jax
    leaves out what compiles in under a second, and a run's dozens of small
    programs would compile again in every process's set-up."""
    import jax

    from triton_dist_tpu.runtime.platform import enable_compile_cache as programs

    programs()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def seed_key(seed: int):
    """The seed as a legacy uint32[2] PRNG key, whole: 64 bits fit, so a
    seed past 2**31 is not folded onto a smaller one."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


# ------------------------------------------------------------- the program


class Telemetry:
    """The program's counters as a difference between two snapshots."""

    def __init__(self, before: dict, after: dict):
        self.before, self.after = before, after

    @staticmethod
    def _sum(snap, kind, name, field, labels):
        tot = 0.0
        for e in snap.get(kind, {}).get(name, []):
            if all(e["labels"].get(k) == v for k, v in labels.items()):
                tot += float(e[field])
        return tot

    def _diff(self, kind, name, field, labels):
        return (self._sum(self.after, kind, name, field, labels)
                - self._sum(self.before, kind, name, field, labels))

    def counter(self, name: str, **labels) -> float:
        return self._diff("counters", name, "value", labels)

    def histogram(self, name: str, **labels) -> tuple[float, float]:
        """(sum, count) observed between the snapshots."""
        return (self._diff("histograms", name, "sum", labels),
                self._diff("histograms", name, "count", labels))

    def digest(self, name: str, **labels) -> tuple[float, float]:
        return (self._diff("digests", name, "sum", labels),
                self._diff("digests", name, "n", labels))


def _snapshot() -> dict:
    from triton_dist_tpu.runtime import telemetry

    snap = telemetry.snapshot()
    return {k: snap[k] for k in ("counters", "histograms", "digests")}


class Loop:
    """One server driven from this one thread: after every ``step()`` the
    mix's source says which requests are due (a closed loop: one for each
    client whose last request ended)."""

    def __init__(self, server, source):
        self.server = server
        self.source = source
        self.reqs: list = []
        self.step_id = 0
        self.step_s: list = []  # seconds each loop iteration took
        self.t_open = 0.0
        self._idle: list = []

    def open(self) -> float:
        """Open the window: submit what is due at its start."""
        self.t_open = time.perf_counter()
        for request in self.source.due(0.0, None):
            self.submit(*request)
        return self.t_open

    def submit(self, client: int, prompt: list, max_new: int) -> None:
        log = stats.ReqLog(client, len(prompt), max_new, 0.0, prompt=prompt)

        def on_token(req, token, idx, log=log):
            log.token_t.append(time.perf_counter())
            log.token_step.append(self.step_id)
            log.tokens.append(int(token))

        def on_finish(req, log=log):
            log.finish_t = time.perf_counter()
            log.finish_reason = req.finish_reason
            self._idle.append(log.client)

        log.submit_t = time.perf_counter()
        req = self.server.submit(prompt, max_new, on_token=on_token, on_finish=on_finish)
        if req.reject_reason is not None:
            log.rejected = str(req.reject_reason)
            log.finish_t = log.submit_t
        self.reqs.append(log)

    def step(self, resubmit: bool) -> None:
        import jax

        self.step_id += 1
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(STEP_MARK):
            self.server.step()
        self.step_s.append(time.perf_counter() - t0)
        idle, self._idle = self._idle, []
        if resubmit:
            for request in self.source.due(time.perf_counter() - self.t_open, idle):
                self.submit(*request)

    def busy(self) -> bool:
        s = self.server.scheduler
        return s.queue_depth() > 0 or bool(s.occupancy())

    def drain(self, limit_s: float = 60.0) -> None:
        t_end = time.perf_counter() + limit_s
        while self.busy() and time.perf_counter() < t_end:
            self.step(resubmit=False)


def warm_up(server, cell: Cell, seed: int) -> int:
    """Serve one request of every prompt length of the mix through the
    server itself, more of them than slots at once, each for a full decode
    chunk and a partial one: every program the window will use (buffer
    zeros, prefill, scatter, gather, decode chunk, table push) and no
    other. Returns the number of requests served."""
    vocab = int(cell.cfg["vocab_size"])
    rng = np.random.default_rng([int(seed), 0x3A])
    chunk = int(cell.cfg["serving"]["chunk"])
    loop = Loop(server, None)
    for i, n in enumerate(traffic.prompt_lengths(cell.mix)):
        loop.submit(-1 - i, rng.integers(0, vocab, size=n).tolist(), chunk + 2)
    loop.drain(limit_s=1100.0)
    bad = [r for r in loop.reqs if not r.ok]
    if bad or loop.busy():
        raise RuntimeError(f"warm-up did not finish: {bad[:2]}")
    return len(loop.reqs)


@dataclasses.dataclass
class Run:
    """What one window left behind: everything a per-layer reader may read."""

    cell: Cell
    seed: int
    chips: int
    tp: int
    peaks: dict
    reqs: list
    t_open: float
    t_close: float
    t_drain_end: float
    first_step: int
    last_step: int
    telemetry: Telemetry
    lowered_in_window: int
    setup_s: float = 0.0
    traced_s: float = 0.0
    traced_first_step: int = 0  # the loop iterations the profiler saw
    traced_last_step: int = -1
    build_s: float = 0.0  # of set-up: the build file's ``build``
    warm_up_s: float = 0.0  # of set-up: the warm-up requests
    longest_steps_s: list = dataclasses.field(default_factory=list)
    memory_peak_bytes: int | None = None
    trace: dict | None = None

    stats = stats
    trace_mod = trace_mod

    @property
    def cfg(self) -> dict:
        return self.cell.cfg

    @property
    def counts(self):
        return self.cell.counts

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def window_steps(self):
        """Per loop iteration inside the window: ``{"prefill": [prompt
        lengths whose first token it streamed], "decode": {request: (rows,
        first row's attended length)}}``, from the harness's token log."""
        return self.steps_between(self.first_step, self.last_step)

    def traced_steps(self):
        """The same for the loop iterations the profiler saw, or None where
        the trace does not hold one ``server.step`` mark for each of them
        (then its programs cannot be laid against the token log)."""
        n = self.traced_last_step - self.traced_first_step + 1
        if self.trace is None or n < 1 or len(self.trace["steps"]) != n:
            return None
        return self.steps_between(self.traced_first_step, self.traced_last_step)

    def steps_between(self, first: int, last: int):
        steps: dict = {}
        for r in self.reqs:
            for i, s in enumerate(r.token_step):
                if not first <= s <= last:
                    continue
                st = steps.setdefault(s, {"prefill": [], "decode": {}})
                if i == 0:
                    st["prefill"].append(r.prompt_len)
                else:
                    n, attended = st["decode"].get(id(r), (0, r.prompt_len + i))
                    st["decode"][id(r)] = (n + 1, attended)
        return steps


def serve_window(loop: Loop, cell: Cell, seconds: float, lowerings: Lowerings,
                 trace_dir: str | None, chips: int, peak_table: dict, seed: int) -> Run:
    """The measured window, the traced seconds after it (``trace_dir``),
    and the drain."""
    import jax

    before = _snapshot()
    lowered0 = lowerings.n
    first_step = loop.step_id + 1
    t_open = loop.open()
    t_close = t_open + float(seconds)
    while time.perf_counter() < t_close:
        loop.step(resubmit=True)
    after = _snapshot()
    last_step = loop.step_id
    lowered = lowerings.n - lowered0
    traced = None
    traced_first = loop.step_id + 1
    if trace_dir is not None:
        # The same steady loop goes on while the profiler runs, so that
        # neither its start nor its stop (seconds of host time) falls into
        # the window the other metrics are taken from.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        span_s = float(cell.mix.get("trace", {}).get("seconds", 2.0))
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            t_stop = time.perf_counter() + span_s
            while time.perf_counter() < t_stop:
                loop.step(resubmit=True)
        finally:
            jax.profiler.stop_trace()
        traced = trace_dir
    traced_last = loop.step_id
    t_traced = time.perf_counter()
    loop.drain()
    t_drain_end = time.perf_counter()
    run = Run(
        cell=cell, seed=seed, chips=chips, tp=int(cell.cfg["serving"]["tp"]),
        peaks=peak_table, reqs=loop.reqs, t_open=t_open, t_close=t_close,
        t_drain_end=t_drain_end, first_step=first_step, last_step=last_step,
        telemetry=Telemetry(before, after), lowered_in_window=lowered,
        traced_s=t_traced - t_close, traced_first_step=traced_first,
        traced_last_step=traced_last,
    )
    run.longest_steps_s = sorted(loop.step_s[first_step - 1:last_step], reverse=True)[:3]
    if traced is not None:
        run.trace = trace_mod.reduce(
            trace_mod.load(trace_mod.find_xplane(traced)), marker=STEP_MARK,
            rehearsal=peak_table is None)
    return run


# -------------------------------------------------------------- the metrics


def end_to_end(run: Run) -> dict:
    out = {}
    for entry, mod in run.cell.end_to_end:
        value = mod.read(run)
        if value is None:
            raise RuntimeError(f"{entry['name']}: nothing to take it from in this window")
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def per_layer(run: Run) -> dict:
    out = {}
    for entry, mod in run.cell.per_layer:
        value = mod.read(run)
        if value is not None:  # a reader that finds nothing returns nothing
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def device_info(devices, memory_peak: int | None) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
            "memory_peak_bytes": memory_peak}


def memory_peak(devices) -> int | None:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def decide(cell: Cell, run: Run, numbers: dict) -> tuple[bool, dict]:
    """Each number compared beside its limit; ``correct`` is all of them."""
    in_win = stats.in_window(run.reqs, run.t_open, run.t_close)
    bad = sum(1 for r in in_win if not r.ok)
    want = int(cell.limits["min_tokens_compared"])
    # Every entry of the cell's limits file that states a ``limit`` is a
    # number of the comparison held to it; the readings it was set from sit
    # beside it in the file.
    compared = {name: [numbers.get(name), spec["limit"]]
                for name, spec in cell.limits.items()
                if isinstance(spec, dict) and "limit" in spec}
    if not compared:
        raise ValueError(f"the limits of {cell.name} hold no number to its reference")
    compared["bad_requests"] = [bad, 0]
    compared["tokens_short"] = [max(want - numbers.get("tokens_compared", 0), 0), 0]
    ok = all(v is not None and v <= lim for v, lim in compared.values())
    return ok, compared


def note(out, **fields) -> None:
    print(json.dumps(fields), file=out, flush=True)


# ------------------------------------------------------------------- a run


def run_cell(manifest_path, workload: str, seed: int, seconds: float, trace: bool,
             devices, t_start: float | None = None, control: bool = False,
             out=None, err=None, tamper=None, root=None) -> dict:
    """One run of one cell on ``devices``. Prints facts on earlier lines
    and the result as the last line of ``out``; returns the result.
    ``control`` also puts the control (the reference in the next precision
    below the stated one) in the program's place and holds it to the same
    limits, for the calibration and the tests: the benchmark's own runs
    never do. ``tamper`` is called with (model, engine, server) after
    warm-up, for the tests that break the timed path underneath."""
    out = out or sys.stdout
    err = err or sys.stderr
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(manifest_path, workload, root)
    devices = list(devices)[: cell.chips]
    if len(devices) < cell.chips:
        raise RuntimeError(f"cell needs {cell.chips} device(s), got {len(devices)}")
    dev = {"platform": devices[0].platform, "device_kind": devices[0].device_kind,
           "device_count": len(devices)}
    try:
        peak_table = peaks.peaks_for(devices[0].device_kind)
    except KeyError:
        if devices[0].platform == "tpu":
            raise
        peak_table = None  # a rehearsal off the chip: no share of a peak is reported
    lowerings = Lowerings()
    t_enter = time.perf_counter()
    model, engine, server = cell.build.build(cell.cfg, seed_key(seed), devices)
    t_built = time.perf_counter()
    warmed = warm_up(server, cell, seed)
    t_warm = time.perf_counter()
    if tamper is not None:
        tamper(model, engine, server)
    loop = Loop(server, cell.loop.source(cell.mix, seed, int(cell.cfg["vocab_size"])))
    setup_s = time.perf_counter() - t_start
    note(out, phase="setup", **dev, workload=workload, seed=seed, setup_s=setup_s,
         import_s=t_enter - t_start, build_s=t_built - t_enter,
         warm_up_s=t_warm - t_built, warm_up_requests=warmed,
         lowerings_in_setup=lowerings.n)

    with (tempfile.TemporaryDirectory(prefix="bench_trace_") if trace
          else contextlib.nullcontext()) as trace_dir:
        run = serve_window(loop, cell, seconds, lowerings, trace_dir,
                           len(devices), peak_table, seed)
    run.memory_peak_bytes = memory_peak(devices)
    run.setup_s, run.build_s, run.warm_up_s = setup_s, t_built - t_enter, t_warm - t_built

    from triton_dist_tpu.runtime import telemetry

    in_win = stats.in_window(run.reqs, run.t_open, run.t_close)
    note(out, phase="window", **dev, seconds=run.window_s,
         requests_submitted=len(in_win),
         requests_finished=sum(1 for r in in_win if r.ok),
         tokens_in_window=stats.tokens_between(run.reqs, run.t_open, run.t_close),
         tpot_samples=len(stats.tpot_ms(run.reqs, run.t_open, run.t_close)),
         drain_s=run.t_drain_end - run.t_close - run.traced_s,
         lowerings_in_window=run.lowered_in_window, longest_steps_s=run.longest_steps_s,
         zero_counters={n: telemetry.counter_total(n) for n in ZERO_COUNTERS},
         backend=engine.backend)

    if trace:
        note(out, phase="trace", **dev, seconds=run.trace["window_s"],
             steps_marked=len(run.trace["steps"]),
             steps_traced=run.traced_last_step - run.traced_first_step + 1,
             programs=trace_mod.program_totals(run.trace))
    metrics = per_layer(run) if trace else end_to_end(run)
    sample = correct_mod.choose(in_win, int(cell.mix["check_requests"]), seed)
    cell.build.release(model, engine, server)
    del model, engine, server, loop
    gc.collect()

    t_check = time.perf_counter()
    numbers: dict = {}
    if sample:
        seq_len = -(-(max(cell.mix["prompt_len"]["values"])
                      + max(cell.mix["max_new"]["values"])) // 128) * 128
        weights = cell.reference.make_weights(cell.cfg, seed_key(seed), devices)
        numbers = correct_mod.compare(
            cell.reference, cell.cfg, weights, sample, seq_len,
            max(cell.mix["max_new"]["values"]), control=control)
        del weights
    in_place = numbers.pop("control", None)
    ok, compared = decide(cell, run, numbers)
    note(out, phase="check", **dev, seconds=time.perf_counter() - t_check, **numbers)

    result = {
        "correct": bool(ok), "attempted": len(in_win),
        "failed": compared["bad_requests"][0],
        "metrics": metrics,
        "device": device_info(devices, run.memory_peak_bytes),
    }
    if trace:
        result["device"]["busy_s"] = run.trace["busy_s"]
        result["device"]["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["numbers"] = numbers
    if control:
        # The control in the served tokens' place, under the same decision.
        ctl_ok, ctl_compared = decide(cell, run, in_place or {})
        result["control"] = {"correct": bool(ctl_ok), "compared": ctl_compared,
                             "precision": (in_place or {}).get("precision")}
    result["compared"] = compared
    print("compared (value, limit): " + json.dumps(compared), file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result
