"""Run one cell of ``BENCHMARK.json`` once, in this process.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up imports the program, makes the inputs from the seed, builds the
program as a user does, and takes the traffic mix's warm-up steps through
the window's own call and feed; the first of them are the steps the
reference follows. Its clock starts when the device runtime has handed over
the chips: importing jax and starting the TPU client took 6.7 to 10.1 s on
the same code from one run to the next (PERF.md section 6), which is the
machine's and not the repo's, so it is printed beside the stages and not
counted. The window
then drives steps for ``--seconds``. After it: peak memory, the comparison
with the plain reference, and with ``--trace 1`` the reduction of the
profiler's trace. The last line of standard output is the result.
"""

import time

T0 = time.perf_counter()  # the process's start, before jax is imported

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from . import check, inputs, readers, spec  # noqa: E402


class CompileMeter:
    """Backend compilations, their seconds and the persistent cache's hits,
    from JAX's own monitoring events. A hit still passes through the
    compile event, in the time the retrieval takes (``chip_smoke.py``)."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def cache_misses(self) -> int:
        return self.compiles - self.cache_hits


class Spans:
    """The benchmark's host spans: kept in memory by the host's clock, and
    written into the profiler's trace (``TraceAnnotation``) so that idle
    gaps of the device can be laid against them."""

    def __init__(self):
        import jax

        self.seconds: dict = {}
        self._annotation = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def __call__(self, name: str):
        with self._annotation("chipbench." + name):
            t = time.perf_counter()
            try:
                yield
            finally:
                self.seconds.setdefault(name, []).append(
                    time.perf_counter() - t)


def require_chips(chips: int):
    """The devices the cell runs on, or exit before anything is built."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"chipbench: the cell needs {chips} TPU chip(s), found "
              f"{len(devices)} x platform={devices[0].platform!r}; there is "
              "no fallback", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def drive_window(program, feed, first_step: int, seconds: float, spans):
    """Steps for ``seconds``: one step in flight while the previous step's
    loss is read on the host. Returns the losses, each step's completion
    time (its loss ready on the host), the window's start and end, and the
    handles of each step's tier hits."""
    losses, done, hits = [], [], []
    pending = None
    i = first_step
    with spans("window"):
        start = time.perf_counter()
        while True:
            with spans("step"):
                loss = program.step(feed.seeds(i), feed.key(i))
            hits.append(program.tier_hits())
            i += 1
            if pending is not None:
                with spans("loss_read"):
                    losses.append(float(pending))
                done.append(time.perf_counter())
            pending = loss
            if time.perf_counter() - start >= seconds:
                break
        with spans("drain"):
            losses.append(float(pending))
        end = time.perf_counter()
        done.append(end)
    return losses, done, start, end, hits


def work_counts(cfg: dict, blocks: list, gathered_rows: float) -> dict:
    """What ``work.py`` reads, per worker and step: valid counts averaged
    over the followed steps' blocks, shapes from the configuration."""
    flat = [b for step in blocks for b in step]
    fanout = list(cfg["fanout"])
    hops = []
    for h, k in enumerate(fanout):
        layer = len(fanout) - 1 - h  # blocks hold the input layer first
        targets, edges, unique = [], [], []
        for b in flat:
            src, dst, _ = b.layers[layer]
            valid = src >= 0
            edges.append(int(valid.sum()))
            targets.append(int(np.unique(dst[valid]).shape[0]))
            deeper = b.layers[layer - 1][1] if layer else None
            unique.append(int(np.unique(deeper[deeper >= 0]).shape[0])
                          if deeper is not None else int((b.n_id >= 0).sum()))
        hops.append({"fanout": k, "targets": float(np.mean(targets)),
                     "edges": float(np.mean(edges)),
                     "unique": float(np.mean(unique))})
    return {
        "hops": hops,
        "gathered_rows": gathered_rows,
        "feature_dim": int(cfg["feature_dim"]),
        "feature_itemsize": int(np.dtype(cfg["feature_dtype"]).itemsize),
        "model": cfg["model"],
        "layer_dims": spec.load_model(
            cfg["model"], "reference").layer_dims(cfg),
    }


def peak_bytes(device) -> int:
    """The device's peak as its runtime reports it: the largest sum of live
    arrays, and the largest reservation for a running program's temporaries,
    which the TPU runtime keeps apart (``peak_bytes_reserved``; it equals
    the step executable's ``temp_size_in_bytes``)."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def trace_dir() -> str:
    return os.path.join(os.environ.get("TMPDIR") or os.path.join(
        spec.ROOT, ".chipbench_tmp"), "chipbench_trace")


def traced(bench: dict, workload: str, tdir: str, device: dict,
           ctx: dict) -> dict:
    """Reduce the run's trace: the cell's per-layer metrics (those whose
    reader found something to read) and the breakdown; ``device`` gains the
    busy and window seconds."""
    from . import xplane

    files = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise SystemExit(f"chipbench: expected one trace, found {files}")
    trace = xplane.load(files[0])
    if os.environ.get("CHIPBENCH_KEEP_TRACE"):
        # how tests/data/recorded.json.gz was made: see the README
        os.makedirs(os.environ["CHIPBENCH_KEEP_TRACE"], exist_ok=True)
        shutil.copy(files[0], os.environ["CHIPBENCH_KEEP_TRACE"])
    shutil.rmtree(tdir, ignore_errors=True)
    wanted = spec.metrics_of(bench, workload, "per_layer")
    files_of = {m["name"]: spec.load_metric(m["name"], ctx["model_scope"])
                for m in wanted}
    ctx = dict(ctx, trace=trace, claims=[
        f["args"]["pattern"] for f in files_of.values()
        if f["reader"] == "device_time_by_scope"])
    metrics = {}
    for m in wanted:
        value = readers.read(files_of[m["name"]], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device["busy_s"] = trace.busy_s()
    device["window_s"] = trace.window_s()
    return {"metrics": metrics,
            "breakdown": {"device_ops": trace.top_ops(10),
                          "idle_gaps": trace.idle_gaps(10)}}


def run(args, devices, compiled: CompileMeter, setup_start: float,
        stages: dict) -> dict:
    """Everything after the device runtime is up; ``stages`` holds the
    set-up stages timed so far and gains the rest."""
    import jax

    from .adapter import Program

    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.load_config(cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    spans = Spans()

    t = time.perf_counter()
    data = inputs.make_inputs(cfg, args.seed)
    weights0 = inputs.make_weights(cfg, args.seed)
    stages["inputs"] = time.perf_counter() - t

    t = time.perf_counter()
    program = Program(cfg, traffic, data, weights0, devices)
    feed = inputs.Feed.of(data, program.global_batch, args.seed)
    stages["place"] = time.perf_counter() - t

    # the warm-up steps go through the window's own call and feed; the
    # first of them are the ones the reference follows after the window
    t = time.perf_counter()
    followed, warmup = int(traffic["followed_steps"]), int(traffic["warmup_steps"])
    if not 1 <= followed <= warmup:
        raise ValueError("followed_steps must lie in [1, warmup_steps]")
    first_losses, first_moment, params_after = [], None, None
    for i in range(warmup):
        loss = program.step(feed.seeds(i), feed.key(i))
        if i < followed:
            first_losses.append(float(loss))
        if i == 0:
            first_moment = program.first_moment_host()
        if i == followed - 1:
            params_after = program.params_host()
    jax.block_until_ready(program.params)
    stages["warm"] = time.perf_counter() - t
    print("set-up: " + " ".join(f"{k} {v:.2f}s" for k, v in stages.items())
          + f"; {compiled.compiles} compilations in "
          f"{compiled.compile_s:.2f}s, {compiled.cache_misses} not from the "
          f"cache; first losses {first_losses}", flush=True)

    seconds = float(args.seconds)
    tdir = trace_dir()
    if args.trace:
        # a trace of the whole window cannot be reduced within the run's
        # time limit: a traced run measures the mix's `trace_seconds`
        seconds = min(seconds, float(traffic["trace_seconds"]))
        shutil.rmtree(tdir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the host spans are the benchmark's own
        jax.profiler.start_trace(tdir, profiler_options=options)
    compiles_before = compiled.compiles
    setup_s = time.perf_counter() - setup_start
    losses, done, start, end, hits = drive_window(
        program, feed, warmup, seconds, spans)
    if args.trace:
        jax.profiler.stop_trace()
    if compiled.compiles != compiles_before:
        print(f"chipbench: {compiled.compiles - compiles_before} compilation(s) "
              "inside the measured window", file=sys.stderr)
        raise SystemExit(3)

    steps = len(losses)
    wall = end - start
    intervals = np.diff(np.asarray([start] + done)) * 1e3
    peak = max(peak_bytes(d) for d in devices)
    rows = float(np.mean([np.asarray(h).sum() for h in hits])) / program.workers
    metrics = {
        "setup_s": setup_s,
        "seeds_per_s": steps * program.global_batch / wall,
        "step_p95_ms": float(np.percentile(intervals, 95)),
        "peak_hbm_gib": peak / 2**30,
    }
    print(f"window: {steps} steps in {wall:.3f}s, step median "
          f"{statistics.median(intervals):.2f} ms p95 "
          f"{metrics['step_p95_ms']:.2f} ms max {intervals.max():.2f} ms; "
          f"losses {losses[0]:.4f} -> {losses[-1]:.4f}", flush=True)

    # the comparison: after the window, after the peak was read, with the
    # program's state freed before the reference takes the device
    obs = check.Observed(
        first_losses, first_moment, params_after,
        [program.blocks(feed.seeds(i), feed.key(i)) for i in range(followed)],
        [program.worker_seeds(feed.seeds(i)) for i in range(followed)],
    )
    program.close()
    compared = check.compare(cfg, data, weights0, obs, args.seed)
    values = dict(compared["numbers"])
    values["block_overflow"] = float(sum(
        b.overflow for step in obs.blocks for b in step))
    values["nonfinite_losses"] = float(
        np.count_nonzero(~np.isfinite(losses)))
    correct, table = check.verdict(values, cfg["limits"])
    print(f"reference losses {compared['losses']['reference']}, program "
          f"{compared['losses']['program']}; blocks {compared['block_detail']}",
          flush=True)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": steps,
              "failed": int(values["nonfinite_losses"])}
    if not args.trace:
        wanted = spec.metrics_of(bench, args.workload, "end_to_end")
        result["metrics"] = {
            m["name"]: {"value": metrics[m["name"]], "unit": m["unit"]}
            for m in wanted}
    else:
        counters = {"compile_s": compiled.compile_s,
                    "cache_misses": float(compiled.cache_misses),
                    "compilations": float(compiled.compiles)}
        result.update(traced(
            bench, args.workload, tdir, device,
            {"steps": steps, "spans": spans.seconds, "counters": counters,
             "stages": stages, "work": work_counts(cfg, obs.blocks, rows),
             "peaks": spec.load_peaks(dev.device_kind),
             "chips": len(devices), "workers": program.workers,
             "model_scope": program.model_file.SCOPE}))
    result["device"] = device
    result["compared"] = table
    check.report(table)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    chips = spec.cell(spec.load_benchmark(), args.workload)["chips"]
    devices = require_chips(chips)
    # the device runtime is up: set-up starts
    setup_start = time.perf_counter()
    print(f"chipbench: {setup_start - T0:.2f}s from the process's start "
          "until the device runtime handed over its devices (not set-up)",
          flush=True)
    from .adapter import enable_compile_cache

    cache_dir = enable_compile_cache()
    compiled = CompileMeter()
    stages = {"import": time.perf_counter() - setup_start}
    print(f"chipbench: {args.workload} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}; compile cache {cache_dir}", flush=True)
    result = run(args, devices, compiled, setup_start, stages)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
