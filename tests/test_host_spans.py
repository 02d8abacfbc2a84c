"""The trainer's host side says what it is doing.

``DistributedTrainer.step`` is a parent and five leaves on the profiler's
clock (``utils.trace.host_span``), the same six stages in its
``StepTimeline`` and, with an enabled ``Tracer``, the same six spans; a
launch that compiled is put down to its step. The names are an interface:
PERF.md section 7 holds the metric files that will read them, and a
reader of a capture lays the device's idle gaps against the leaves. They
are tested where they are written, at a tiny size on the CPU, from ONE
profiler capture for the module, read back with ``ProfileData`` as
``chipbench/xplane.py::load`` reads a chip's.
"""

import glob
import logging
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import quiver_tpu as quiver
from quiver_tpu.models.sage import GraphSAGE
from quiver_tpu.obs import Tracer, compile_watch
from quiver_tpu.parallel.mesh import make_mesh
from quiver_tpu.parallel.trainer import DistributedTrainer
from quiver_tpu.utils import trace
from quiver_tpu.utils.graphgen import generate_pareto_graph

# (f) The interface. A rename of any of these is a `benchmark` matter, as
# tests/test_step_scopes.py says of the device scopes: the per-layer metrics
# host_{tune,pack,place,launch,record}_ms (PERF.md section 7) read the
# leaves by these names, and the idle gaps are laid against them.
PARENT = "quiver.step"
LEAVES = ["quiver.step.tune", "quiver.step.pack", "quiver.step.place",
          "quiver.step.launch", "quiver.step.record"]
STAGES = ["step", "step.tune", "step.pack", "step.place", "step.launch",
          "step.record"]
COUNTERS = ["xla.compiles", "xla.compile_seconds", "xla.cache_hits"]

MESHES = {"one": dict(data=1, feature=1), "clique": dict(data=2, feature=2)}
BATCH = 8
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build(data, feature, tracer=None):
    ei = generate_pareto_graph(300, 6.0, seed=0)
    topo = quiver.CSRTopo(edge_index=ei)
    sampler = quiver.GraphSageSampler(topo, [3, 2], frontier_caps=[32, 64])
    mesh = make_mesh(data=data, feature=feature,
                     devices=jax.devices()[:data * feature])
    rows = np.random.default_rng(0).normal(
        size=(topo.node_count, 8)).astype(np.float32)
    if feature > 1:
        store = quiver.ShardedFeature(
            mesh, device_cache_size=rows.nbytes // feature, csr_topo=topo)
    else:
        store = quiver.Feature(device_cache_size=rows.nbytes, csr_topo=topo)
    trainer = DistributedTrainer(
        mesh, sampler, store.from_cpu_tensor(rows),
        GraphSAGE(hidden=8, num_classes=4, num_layers=2), optax.adam(1e-2),
        local_batch=BATCH, seed_sharding="all" if feature > 1 else "data",
        tracer=tracer)
    params, opt_state = trainer.init(jax.random.PRNGKey(0))
    labels = jnp.asarray(
        np.random.default_rng(1).integers(0, 4, topo.node_count), jnp.int32)
    return trainer, params, opt_state, labels


class Driven:
    """A trainer and what it takes to step it."""

    def __init__(self, shape, tracer=None):
        self.trainer, self.params, self.opt_state, self.labels = build(
            **shape, tracer=tracer)
        self.calls = 0

    def steps(self, n):
        rng = np.random.default_rng(self.calls)
        for _ in range(n):
            seeds = rng.integers(0, 300, self.trainer.global_batch)
            self.params, self.opt_state, loss = self.trainer.step(
                self.params, self.opt_state, seeds, self.labels,
                jax.random.PRNGKey(self.calls))
            self.calls += 1
        jax.block_until_ready(loss)

    def lowered(self, debug_info=False):
        """The text of a step program built and traced anew."""
        t = self.trainer
        seeds = jnp.asarray(t.shard_seeds(np.arange(t.global_batch)))
        return t._build().lower(
            self.params, self.opt_state, t.topo, t._feature_parts(), seeds,
            self.labels, jax.random.PRNGKey(1), np.asarray(False),
        ).as_text(debug_info=debug_info)


def state_of(trainer):
    """What an operator sees of the compiles: health, registry, timeline."""
    compile_stage = trainer.timeline.stats("step.compile")
    return {
        "last_compile_step": trainer.health()["last_compile_step"],
        "compile_stages": 0 if compile_stage is None else compile_stage.count,
        **{name: (None if trainer.metrics.value(name) is None
                  else float(trainer.metrics.value(name)))
           for name in COUNTERS},
    }


def stage_totals(trainer):
    return {name: (s.count, s.total, s.max)
            for name, s in trainer.timeline.summary().items()}


class _Records(logging.Handler):
    def __init__(self):
        super().__init__(logging.INFO)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Every mesh driven under one capture. Per mesh, each part between
    marks of the test's own: ``on`` three steps with tracing enabled and an
    enabled ``Tracer``; ``more`` three more of the same trainer; ``rekey``
    ``refresh()`` and one step; ``off`` three steps of a second trainer
    with tracing disabled."""
    logger = trace.get_logger()
    records = _Records()
    level = logger.level
    logger.addHandler(records)
    logger.setLevel(logging.INFO)
    tdir = str(tmp_path_factory.mktemp("host_spans"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as chipbench/run.py takes its traces
    out = {}
    jax.profiler.start_trace(tdir, profiler_options=options)
    try:
        for mesh, shape in MESHES.items():
            o = out[mesh] = {}
            trace.enable_trace()
            on = o["on_run"] = Driven(shape, Tracer(enabled=True))
            with jax.profiler.TraceAnnotation(f"test.{mesh}.on"):
                on.steps(3)
            o["on"] = state_of(on.trainer)
            o["on_spans"] = on.trainer.tracer.spans()
            o["on_stages"] = stage_totals(on.trainer)
            o["report"] = on.trainer.metrics_report()
            with jax.profiler.TraceAnnotation(f"test.{mesh}.more"):
                on.steps(3)
            o["more"] = state_of(on.trainer)
            del records.messages[:]
            on.trainer.refresh()
            with jax.profiler.TraceAnnotation(f"test.{mesh}.rekey"):
                on.steps(1)
            o["rekey"] = state_of(on.trainer)
            o["rekey_log"] = list(records.messages)
            trace.disable_trace()
            off = o["off_run"] = Driven(shape)
            with jax.profiler.TraceAnnotation(f"test.{mesh}.off"):
                off.steps(3)
            o["off_stages"] = stage_totals(off.trainer)
    finally:
        jax.profiler.stop_trace()
        trace._enabled = None
        logger.removeHandler(records)
        logger.setLevel(level)
    files = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1, files
    events = []
    for plane in jax.profiler.ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("quiver.", "test.")):
                    start = int(ev.start_ns)
                    events.append((ev.name, start,
                                   start + int(ev.duration_ns),
                                   dict(ev.stats)))
    out["events"] = sorted(events, key=lambda e: (e[1], -e[2]))
    return out


def inside(runs, mark):
    """The program's events between the edges of the test's mark."""
    (lo, hi), = [(s, e) for name, s, e, _ in runs["events"] if name == mark]
    return [ev for ev in runs["events"]
            if ev[0].startswith("quiver.") and lo <= ev[1] and ev[2] <= hi]


def steps_of(events):
    """[(parent, [its leaves in time order])]: a leaf belongs to the
    parent whose interval holds it."""
    parents = [ev for ev in events if ev[0] == PARENT]
    leaves = [ev for ev in events if ev[0] in LEAVES]
    return [(p, [lf for lf in leaves if p[1] <= lf[1] and lf[2] <= p[2]])
            for p in parents]


@pytest.mark.parametrize("mesh", list(MESHES))
def test_three_steps_are_three_indexed_parents_holding_their_leaves(
        runs, mesh):
    steps = steps_of(inside(runs, f"test.{mesh}.on"))
    assert [p[3].get("step") for p, _ in steps] == [0, 1, 2]
    for parent, leaves in steps:
        assert [lf[0] for lf in leaves] == LEAVES  # the table's order
        for before, after in zip(leaves, leaves[1:]):
            assert before[2] <= after[1]  # disjoint
        covered = sum(lf[2] - lf[1] for lf in leaves)
        assert covered >= 0.9 * (parent[2] - parent[1]), (parent, leaves)
    # every leaf of the part belongs to one of its parents
    held = sum(len(leaves) for _, leaves in steps)
    assert held == len([ev for ev in inside(runs, f"test.{mesh}.on")
                        if ev[0] in LEAVES]) == 15


@pytest.mark.parametrize("mesh", list(MESHES))
def test_tracing_disabled_leaves_no_event_and_the_timeline_whole(runs, mesh):
    assert inside(runs, f"test.{mesh}.off") == []
    stages = runs[mesh]["off_stages"]
    assert {name: stages[name][0] for name in STAGES} == dict.fromkeys(
        STAGES, 3)
    # the first launch compiled there too: the watch needs no tracing
    assert runs[mesh]["off_run"].trainer.health()["last_compile_step"] == 0


@pytest.mark.parametrize("mesh", list(MESHES))
def test_the_lowered_step_does_not_know_of_tracing(runs, mesh):
    run = runs[mesh]["off_run"]
    try:
        trace.enable_trace()
        enabled = run.lowered()
        named = run.lowered(debug_info=True)
        trace.disable_trace()
        disabled = run.lowered()
    finally:
        trace._enabled = None
    assert enabled == disabled
    # the device scopes are there (so the text does carry names) and no
    # host name is: not the prefix, not a leaf
    assert "sample_layer_0" in named and "step_metrics" in named
    assert "quiver." not in named
    for stage in STAGES[1:]:
        assert stage not in named


@pytest.mark.parametrize("mesh", list(MESHES))
def test_a_compile_is_put_down_to_the_step_that_paid_it(runs, mesh):
    o = runs[mesh]
    launches = [lf for _, leaves in steps_of(inside(runs, f"test.{mesh}.on"))
                for lf in leaves if lf[0] == "quiver.step.launch"]
    assert launches[0][3].get("compiles", 0) >= 1
    assert all("compiles" not in lf[3] for lf in launches[1:])
    first = o["on"]
    assert first["last_compile_step"] == 0 and first["compile_stages"] == 1
    assert first["xla.compiles"] >= 1 and first["xla.compile_seconds"] > 0
    assert first["xla.cache_hits"] == 0  # no persistent cache in the tests
    for name in COUNTERS:
        assert name in o["report"]
    # three more steps compile nothing and move none of it
    assert o["more"] == first
    more = steps_of(inside(runs, f"test.{mesh}.more"))
    assert [p[3]["step"] for p, _ in more] == [3, 4, 5]
    assert all("compiles" not in lf[3] for _, leaves in more for lf in leaves)
    # refresh() rebuilds the jitted step: the next call, index 6, compiles,
    # and the span, the registry, health() and the log all name it
    (parent, leaves), = steps_of(inside(runs, f"test.{mesh}.rekey"))
    assert parent[3]["step"] == 6
    assert leaves[3][0] == "quiver.step.launch"
    assert leaves[3][3].get("compiles", 0) >= 1
    rekey = o["rekey"]
    assert rekey["last_compile_step"] == 6 and rekey["compile_stages"] == 2
    assert rekey["xla.compiles"] > first["xla.compiles"]
    assert rekey["xla.compile_seconds"] > first["xla.compile_seconds"]
    assert any(m.startswith("step 6 compiled: ") for m in o["rekey_log"]), (
        o["rekey_log"])


@pytest.mark.parametrize("mesh", list(MESHES))
def test_the_tracers_children_are_the_timelines_readings(runs, mesh):
    spans = runs[mesh]["on_spans"]
    parents = [s for s in spans if s.name == "train.step"]
    assert [s.attrs["step"] for s in parents] == [0, 1, 2]
    seconds = dict.fromkeys(STAGES, 0.0)
    for parent in parents:
        assert parent.trace_id == f"train.step.{parent.attrs['step']}"
        children = [s for s in spans if s.parent_id == parent.span_id]
        assert [s.name for s in children] == [
            "train." + stage for stage in STAGES[1:]]
        for child in children:
            assert child.trace_id == parent.trace_id
            assert parent.t0 <= child.t0
            assert child.t0 + child.dur <= parent.t0 + parent.dur
            seconds[child.name[len("train."):]] += child.dur
        seconds["step"] += parent.dur
    # one clock reading, two sinks: the spans' seconds ARE the stages'
    for stage, (count, total, _) in runs[mesh]["on_stages"].items():
        if stage in seconds:
            assert count == 3
            assert abs(total - seconds[stage]) < 1e-6, stage
    assert "compiles" in [s for s in spans
                          if s.name == "train.step.launch"][0].attrs


def test_the_names_are_the_interface(runs):
    for mesh in MESHES:
        # nothing else of the program starts with the parent's name and a
        # dot; `quiver.step_keys`, `quiver.step_metrics` are trace_scope's
        # host halves, entered while the first launch traces the program
        named = {ev[0] for ev in inside(runs, f"test.{mesh}.on")
                 if ev[0] == PARENT or ev[0].startswith(PARENT + ".")}
        assert named == {PARENT, *LEAVES}
        assert set(STAGES) <= set(runs[mesh]["on_stages"])
        report = runs[mesh]["report"]
        for stage in STAGES:
            assert f"\n  {stage} " in report
        for q in ("p50 ms", "p95 ms", "p99 ms"):
            assert q in report
        registry = runs[mesh]["on_run"].trainer.metrics
        assert set(COUNTERS) <= set(registry.names())
    with open(os.path.join(ROOT, "docs", "Introduction.md")) as f:
        doc = f.read()
    for name in [PARENT, *LEAVES, *STAGES[1:], *COUNTERS,
                 "last_compile_step"]:
        assert f"`{name}`" in doc, name


def test_host_span_is_the_one_primitive(runs):
    """``trace_scope``'s host half and ``StepTimeline.stage`` go through
    ``host_span``: prefixed when enabled, nothing when disabled."""
    try:
        trace.disable_trace()
        off = trace.host_span("x", step=1)
        assert off is trace.host_span("y")  # the shared do-nothing
        with off, trace.trace_scope("z"):
            pass
        trace.enable_trace()
        on = trace.host_span("x", step=1)
        assert isinstance(on, jax.profiler.TraceAnnotation)
    finally:
        trace._enabled = None
    # the program's scopes, entered on the host while the first launch
    # traced the step, carry the prefix too and sit inside that launch
    events = inside(runs, "test.one.on")
    launch = [ev for ev in events if ev[0] == "quiver.step.launch"][0]
    scopes = [ev for ev in events if ev[0] == "quiver.sample_layer_0"]
    assert scopes and all(launch[1] <= s[1] and s[2] <= launch[2]
                          for s in scopes)
    assert not [ev for ev in runs["events"]
                if ev[0] in ("step", "sample_layer_0")]


def test_the_compile_watch_counts_jaxs_events_once():
    watch = compile_watch()
    assert compile_watch() is watch
    before = watch.totals
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.25)
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event_duration_secs("/jax/core/compile/other", 9.0)
    after = watch.totals
    assert after is not before
    assert after.compiles - before.compiles == 1  # registered once
    assert after.seconds - before.seconds == pytest.approx(0.25)
    assert after.cache_hits - before.cache_hits == 1
    assert watch.totals is after  # nothing happened: the same tuple
