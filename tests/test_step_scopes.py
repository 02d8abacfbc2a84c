"""The fused step names every phase it runs and counts its own work.

Scope names are an interface (PERF.md section 3 lists them, the per-layer
metric files under ``chipbench/metrics/`` read them), so they are tested
where they are written: in the ``op_name`` metadata of the compiled step, at
a tiny size, with tracing DISABLED. The paths are read from the compiled
module because XLA's inliner is what joins a nested jit's names to its call
site (``jit(body)/reindex_layer_0/dedup/jit(argsort)/...``): the same
text a device trace shows.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import quiver_tpu as quiver
from chipbench import spec
from quiver_tpu.ops.reindex import reindex_layer
from quiver_tpu.parallel.mesh import make_mesh
from quiver_tpu.parallel.trainer import DistributedTrainer
from quiver_tpu.utils import trace
from quiver_tpu.utils.graphgen import generate_pareto_graph

BATCH = 8
BENCH = spec.load_benchmark()

# the tiny programs stand for the benchmark's cells: same hops, same mesh,
# same feature store and seed sharding, the model of the cell's configuration
CELLS = {
    "reddit-sage.hbm": dict(fanout=[3, 2], caps=[32, 64]),
    "products-sage.hbm": dict(fanout=[3, 2, 2], caps=[32, 64, 128]),
    "products-sage.clique2x2": dict(
        fanout=[3, 2, 2], caps=[32, 64, 128], data=2, feature=2),
    "products-gat.hbm": dict(fanout=[3, 2, 2], caps=[32, 64, 128]),
    "mag240m-rsage.hbm": dict(fanout=[3, 2], caps=[32, 64], relations=5),
    "mag240m-rgat.hbm": dict(fanout=[3, 2], caps=[32, 64], relations=5),
}


def model_file(cell):
    """The program's side of the model of the cell's configuration: what
    builds it, and the ``SCOPE`` its ops carry in the step."""
    cfg = spec.load_config(spec.cell(BENCH, cell)["config"])
    return spec.load_model(cfg["model"], "models")


def scope_of(cell) -> str:
    return re.escape(model_file(cell).SCOPE)


SCOPES = "|".join(sorted({scope_of(cell) for cell in CELLS}))

# (b): what may sit outside every scope. Parameters, tuples, copies and
# constants (a broadcast or iota of one included) are data movement the
# compiler arranges; an instruction with no op_name at all is one the
# compiler made itself (a reduce-window tree, a simplified multiply); a
# name that does not start with ``jit(`` is an argument's or a reduction
# region's own (``parts[1]``, ``reduce_sum``); ``broadcast.N`` is how the
# SPMD partitioner names a constant it spread over the shards.
TRIVIAL = {"parameter", "constant", "tuple", "get-tuple-element", "copy",
           "bitcast", "broadcast", "iota"}
PARTITIONER_CONSTANT = re.compile(r"^broadcast\.\d+$")

TOP_LEVEL = re.compile(
    r"^(sample_layer_\d+|reindex_layer_\d+|feature_gather"
    rf"|jvp\((?:{SCOPES})\)|transpose\(jvp\((?:{SCOPES})\)\)"
    r"|seed_loss|jvp\(seed_loss\)|transpose\(jvp\(seed_loss\)\)"
    r"|step_keys|grad_allreduce|optax_update|step_metrics)$")

# the scopes ``GATConv`` writes under ``conv{i}`` (models/gat.py), forward
# and transposed
ATTENTION_SCOPES = ("attn_project", "attn_logits", "attn_softmax",
                    "attn_aggregate", "skip")
# and those of ``RelSAGEConv`` (models/rsage.py)
RELATIONAL_SCOPES = ("rel_aggregate", "rel_transform", "norm")
# and those of ``RelGATConv`` (models/rgat.py)
RGAT_SCOPES = ("rgat_aggregate", "rgat_logits", "rgat_softmax",
               "rgat_transform", "norm")
EITHER_WAY = r"jvp\({model_scope}\)|transpose\(jvp\({model_scope}\)\)"

# (c): the top-level scope each new metric's pattern may reach into;
# ``{model_scope}`` is the scope of the cell's model
NEW_METRICS = {
    "reindex_dedup_device_ms": r"reindex_layer_\d+",
    "reindex_compact_device_ms": r"reindex_layer_\d+",
    "reindex_relabel_device_ms": r"reindex_layer_\d+",
    "reindex_hop0_device_ms": r"reindex_layer_0",
    "reindex_hop1_device_ms": r"reindex_layer_1",
    "reindex_hop2_device_ms": r"reindex_layer_2",
    "gather_hot_device_ms": r"feature_gather",
    "gather_route_device_ms": r"feature_gather",
    "gather_exchange_device_ms": r"feature_gather",
    "allreduce_device_ms": r"grad_allreduce",
    "forward_device_ms": r"jvp\({model_scope}\)",
    "backward_device_ms": r"transpose\(jvp\({model_scope}\)\)",
    "optimizer_device_ms": r"optax_update",
    "attn_project_device_ms": EITHER_WAY,
    "attn_softmax_device_ms": EITHER_WAY,
    "attn_aggregate_device_ms": EITHER_WAY,
    "attn_skip_device_ms": EITHER_WAY,
    "attn_roofline": EITHER_WAY,
    "rel_aggregate_device_ms": EITHER_WAY,
    "rel_transform_device_ms": EITHER_WAY,
    "norm_device_ms": EITHER_WAY,
    "rel_roofline": EITHER_WAY,
    "rgat_aggregate_device_ms": EITHER_WAY,
    "rgat_logits_device_ms": EITHER_WAY,
    "rgat_softmax_device_ms": EITHER_WAY,
    "rgat_transform_device_ms": EITHER_WAY,
    "rgat_roofline": EITHER_WAY,
}
# the scopes under ``conv{i}`` that each metric of the attention may read
ATTENTION_METRICS = {
    "attn_project_device_ms": {"attn_project"},
    "attn_softmax_device_ms": {"attn_logits", "attn_softmax"},
    "attn_aggregate_device_ms": {"attn_aggregate"},
    "attn_skip_device_ms": {"skip"},
    "attn_roofline": {"attn_logits", "attn_softmax", "attn_aggregate"},
}
# the scopes that each metric of the relational layers may read, under
# ``conv{i}`` (and, for the batch norms, the head's ``mlp``)
RELATIONAL_METRICS = {
    "rel_aggregate_device_ms": {"rel_aggregate"},
    "rel_transform_device_ms": {"rel_transform"},
    "norm_device_ms": {"norm"},
    "rel_roofline": {"rel_aggregate"},
}
# and those of the relational attention, under ``conv{i}``
RGAT_METRICS = {
    "rgat_aggregate_device_ms": {"rgat_aggregate"},
    "rgat_logits_device_ms": {"rgat_logits"},
    "rgat_softmax_device_ms": {"rgat_softmax"},
    "rgat_transform_device_ms": {"rgat_transform"},
    "rgat_roofline": {"rgat_aggregate", "rgat_logits", "rgat_softmax"},
}

_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%\S+ = .*?\s?([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PREFIX = re.compile(r"^jit\(\w+\)/(shard_map/)?")


@pytest.fixture(autouse=True)
def _tracing_disabled():
    trace.disable_trace()
    yield
    trace._enabled = None


def build(fanout, caps, data=1, feature=1, cell="products-sage.hbm",
          relations=0, **kwargs):
    ei = generate_pareto_graph(300, 6.0, seed=0)
    topo = quiver.CSRTopo(edge_index=ei)
    if relations:
        topo.set_edge_relation(np.random.default_rng(2).integers(
            0, relations, topo.edge_count), coo_order=False)
    sampler = quiver.GraphSageSampler(
        topo, list(fanout), frontier_caps=list(caps), kernel="xla",
        dedup="scan")
    mesh = make_mesh(data=data, feature=feature,
                     devices=jax.devices()[:data * feature])
    rows = np.random.default_rng(0).normal(
        size=(topo.node_count, 8)).astype(np.float32)
    if feature > 1:
        store = quiver.ShardedFeature(
            mesh, device_cache_size=rows.nbytes // feature, csr_topo=topo,
            kernel="xla")
    else:
        store = quiver.Feature(
            device_cache_size=rows.nbytes, csr_topo=topo, kernel="xla")
    trainer = DistributedTrainer(
        mesh, sampler, store.from_cpu_tensor(rows),
        model_file(cell).build({"hidden": 8, "classes": 4, "heads": 2,
                                "relations": relations,
                                "layers": len(fanout), "dropout": 0.0}),
        optax.adam(1e-2), local_batch=BATCH,
        seed_sharding="all" if feature > 1 else "data", **kwargs)
    params, opt_state = trainer.init(jax.random.PRNGKey(0))
    labels = jnp.asarray(
        np.random.default_rng(1).integers(0, 4, topo.node_count), jnp.int32)
    return trainer, params, opt_state, labels


def step_text(trainer, params, opt_state, labels) -> str:
    """The compiled step, as text."""
    seeds = jnp.asarray(trainer.shard_seeds(np.arange(trainer.global_batch)))
    return trainer._step.lower(
        params, opt_state, trainer.topo, trainer._feature_parts(), seeds,
        labels, jax.random.PRNGKey(1), np.asarray(False),
    ).compile().as_text()


def instructions_of(text):
    """(opcode, scope path below ``jit(body)/[shard_map/]``) of every
    instruction of the compiled step that carries a ``jit(...)`` name."""
    out = []
    for line in text.splitlines():
        inst, name = _INSTRUCTION.match(line), _OP_NAME.search(line)
        if inst and name and name.group(1).startswith("jit("):
            out.append((inst.group(1), _PREFIX.sub("", name.group(1))))
    assert out
    return out


def step_instructions(trainer, params, opt_state, labels):
    return instructions_of(step_text(trainer, params, opt_state, labels))


@pytest.fixture(scope="module")
def texts():
    """The compiled step of each cell's tiny stand-in, lowered once, with
    the length of the edge array as the step holds it."""
    trace.disable_trace()
    out = {}
    for cell, shape in CELLS.items():
        built = build(**shape, cell=cell)
        out[cell] = (step_text(*built), int(built[0].topo.indices.shape[0]))
    return out


@pytest.fixture(scope="module")
def programs(texts):
    return {cell: instructions_of(text) for cell, (text, _) in texts.items()}


def paths_of(instructions):
    return [path for _, path in instructions]


def has(paths, pattern):
    rx = re.compile(pattern)
    return any(rx.search(p) for p in paths)


@pytest.mark.parametrize("cell", list(CELLS))
def test_every_scope_of_the_tree_is_in_the_untraced_program(programs, cell):
    assert not trace.trace_enabled()
    paths = paths_of(programs[cell])
    hops = len(CELLS[cell]["fanout"])
    wanted = [f"^sample_layer_{l}/" for l in range(hops)]
    for l in range(hops):
        wanted += [f"^reindex_layer_{l}/(.*/)?{phase}(/|$)"
                   for phase in ("dedup", "compact", "relabel", "assemble")]
    scope = scope_of(cell)
    wanted += [r"^feature_gather/", r"^feature_gather/tier_hot/",
               rf"^jvp\({scope}\)/", rf"^transpose\(jvp\({scope}\)\)/",
               r"^(jvp\()?seed_loss", r"^step_keys/", r"^grad_allreduce/",
               r"^optax_update/", r"^step_metrics/"]
    if scope == "GAT":
        wanted += [rf"^{way}/conv{l}/{name}/"
                   for way in (r"jvp\(GAT\)", r"transpose\(jvp\(GAT\)\)")
                   for l in range(hops) for name in ATTENTION_SCOPES]
    if scope == "RGraphSAGE":
        ways = (r"jvp\(RGraphSAGE\)", r"transpose\(jvp\(RGraphSAGE\)\)")
        # the input layer's rows are data: its means have no transpose
        wanted += [rf"^{way}/conv{l}/{name}/" for way in ways
                   for l in range(hops) for name in RELATIONAL_SCOPES
                   if (l, name, way) != (0, "rel_aggregate", ways[1])]
        wanted += [rf"^{way}/mlp/(norm/)?" for way in ways]
    if scope == "RGAT":
        ways = (r"jvp\(RGAT\)", r"transpose\(jvp\(RGAT\)\)")
        wanted += [rf"^{way}/conv{l}/{name}/" for way in ways
                   for l in range(hops) for name in RGAT_SCOPES]
        wanted += [rf"^{way}/mlp/(norm/)?" for way in ways]
    if cell.endswith("clique2x2"):
        wanted += [r"^feature_gather/tier_hot/route_plan/",
                   r"^feature_gather/tier_hot/route_exchange/"]
    missing = [w for w in wanted if not has(paths, w)]
    assert not missing, missing
    assert not has(paths, "feature_gather/feature_gather")


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_compaction_has_no_loop_and_keeps_its_scope(programs, cell):
    """``dedup="scan"`` packs the frontier with one sort: no ``while`` (the
    binary search it replaced) under ``compact``, and a ``compact`` path
    at every hop still, so that ``reindex_compact_device_ms`` keeps
    reading."""
    compact = re.compile(r"^reindex_layer_(\d+)/compact(/|$)")
    inside = [(op, path, int(m.group(1)))
              for op, path in programs[cell] if (m := compact.match(path))]
    loops = [path for op, path, _ in inside
             if op == "while" or "searchsorted" in path or "while" in path]
    assert not loops, loops[:5]
    hops = set(range(len(CELLS[cell]["fanout"])))
    assert {hop for _, _, hop in inside} == hops
    assert {hop for op, _, hop in inside if op == "sort"} == hops


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_scan_reindex_sorts_in_every_phase_and_never_gathers(
        programs, cell):
    """``dedup="scan"`` carries every value as the payload of a sort: a
    ``sort`` under each of the three phases at every hop, and no gather
    (an op, or a fusion named for its gather) under ``dedup`` or
    ``relabel``, where the T-lane gathers of the device trace were."""
    phase = re.compile(r"^reindex_layer_(\d+)/(?:.*/)?(dedup|compact|relabel)/")
    inside = [(op, path, int(m.group(1)), m.group(2))
              for op, path in programs[cell] if (m := phase.match(path))]
    gathers = [path for op, path, _, name in inside if name != "compact"
               and (op == "gather" or path.endswith("/gather"))]
    assert not gathers, gathers[:5]
    hops = range(len(CELLS[cell]["fanout"]))
    assert {(hop, name) for op, _, hop, name in inside if op == "sort"} == {
        (hop, name) for hop in hops
        for name in ("dedup", "compact", "relabel")}


@pytest.mark.parametrize("cell", list(CELLS))
def test_every_instruction_lies_under_one_top_level_scope(programs, cell):
    stray = sorted({(op, path) for op, path in programs[cell]
                    if op not in TRIVIAL
                    and not PARTITIONER_CONSTANT.match(path)
                    and not TOP_LEVEL.match(path.split("/")[0])})
    assert not stray, stray[:20]


_DEFINITION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])\S* ([a-z][a-z0-9\-]*)\((.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_SLICE_SIZES = re.compile(r"slice_sizes=\{([\d,]*)\}")


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_sampler_reads_the_edge_array_as_rows_of_128_words(texts, cell):
    """The edge array is placed as whole 128-word blocks and every hop
    reads it through its ``(E'/128, 128)`` view: each gather from it takes
    ``(1, 128)`` slices (none takes one word from an operand of E words),
    nothing in the step copies, pads or slices an array of its size (the
    view is a bitcast of the step's argument), and every op that reads it
    lies under ``sample_layer_{l}``, where ``sample_device_ms`` reads."""
    text, words = texts[cell]
    assert words % 128 == 0
    whole = {f"s32[{words}]", f"s32[{words // 128},128]"}
    shape_of, reads = {}, []
    for line in text.splitlines():
        m = _DEFINITION.match(line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        shape_of[name] = shape
        reads.append((op, [o for o in _OPERAND.findall(rest.split(
            ", metadata=")[0].split(", calls=")[0])], line))
        # the array itself, its view, and nothing made from it at its size
        assert shape not in whole or op in ("parameter", "bitcast"), line[:200]
    hops = set()
    for op, operands, line in reads:
        if not any(shape_of.get(o) in whole for o in operands):
            continue
        if op in ("bitcast", "tuple", "get-tuple-element"):
            continue
        name = _OP_NAME.search(line)
        path = _PREFIX.sub("", name.group(1)) if name else ""
        hop = re.match(r"^sample_layer_(\d+)/", path)
        assert hop, line[:300]
        if op == "gather":
            assert shape_of[operands[0]] == f"s32[{words // 128},128]"
            assert _SLICE_SIZES.search(line).group(1) == "1,128", line[:300]
            hops.add(int(hop.group(1)))
    assert hops == set(range(len(CELLS[cell]["fanout"])))


@pytest.mark.parametrize("metric", list(NEW_METRICS))
def test_new_metric_patterns_read_their_scope_and_nothing_else(
        programs, metric):
    cells = spec.load_metric(metric).get("workloads") or [
        w["name"] for w in BENCH["workloads"]]
    for cell in cells:
        # as a traced run of the cell loads it: the cell's model in the fill
        file = spec.load_metric(metric, model_file(cell).SCOPE)
        assert file["reader"] in ("device_time_by_scope", "roofline")
        rx = re.compile(file["args"]["pattern"])
        inside = re.compile("^(" + NEW_METRICS[metric].replace(
            "{model_scope}", scope_of(cell)) + ")$")
        # the reader searches the whole path, prefix included
        matched = [p for p in paths_of(programs[cell])
                   if rx.search("jit(body)/" + p)]
        assert matched, (metric, cell)
        outside = [p for p in matched if not inside.match(p.split("/")[0])]
        assert not outside, (metric, cell, outside[:5])
        if metric in RELATIONAL_METRICS:
            # its own scopes alone, under every layer (the batch norms'
            # under the head's too), forward and transposed
            read = {tuple(p.split("/")[:3]) for p in matched}
            assert {r[2] for r in read} <= RELATIONAL_METRICS[metric] | {
                "norm"} and {r[2] for r in read if r[1] != "mlp"} == \
                RELATIONAL_METRICS[metric], read
            layers = {f"conv{l}" for l in range(len(CELLS[cell]["fanout"]))}
            if metric == "norm_device_ms":
                layers.add("mlp")
            assert {r[1] for r in read if r[0] == "jvp(RGraphSAGE)"} == \
                layers, read
            # the input layer's rows are data: its means have no transpose
            transposed = {r[1] for r in read
                          if r[0] == "transpose(jvp(RGraphSAGE))"}
            assert layers - {"conv0"} <= transposed <= layers, read
        if metric in RGAT_METRICS:
            # forward and transpose both, every layer, its own scopes alone
            read = {tuple(p.split("/")[:3]) for p in matched}
            assert {r[2] for r in read} == RGAT_METRICS[metric], read
            assert {r[:2] for r in read} == {
                (way, f"conv{l}") for l in range(len(CELLS[cell]["fanout"]))
                for way in ("jvp(RGAT)", "transpose(jvp(RGAT))")}, read
        if metric in ATTENTION_METRICS:
            # forward and transpose both, every layer, its own scopes alone
            read = {tuple(p.split("/")[:3]) for p in matched}
            assert {r[2] for r in read} == ATTENTION_METRICS[metric], read
            assert {r[:2] for r in read} == {
                (way, f"conv{l}") for l in range(len(CELLS[cell]["fanout"]))
                for way in ("jvp(GAT)", "transpose(jvp(GAT))")}, read


def test_accepted_patterns_still_claim_the_gather_and_the_optimizer(programs):
    gather = re.compile(spec.load_metric("gather_device_ms")["args"]["pattern"])
    clique = programs["products-sage.clique2x2"]
    for cell, instructions in programs.items():
        model = re.compile(spec.load_metric(
            "model_device_ms", model_file(cell).SCOPE)["args"]["pattern"])
        rows = [p for op, p in instructions
                if op in ("gather", "fusion") and "tier_hot" in p
                and p.endswith("/gather")]
        assert rows and all(gather.search(p) for p in rows), cell
        update = [p for _, p in instructions if p.startswith("optax_update")]
        assert update and all(model.search(p) for p in update), cell
        # the loss's label gather is the model's no more the gather's
        assert not any(gather.search(p) for _, p in instructions
                       if "seed_loss" in p), cell
    exchanged = [p for op, p in clique if op == "all-to-all"]
    assert exchanged and all(gather.search(p) for p in exchanged)


@pytest.fixture(scope="module")
def capped_clique():
    """The clique's stand-in with buckets that can overflow: with two
    shards the default budget (alpha 2) makes full-length buckets."""
    trace.disable_trace()
    return step_instructions(*build(
        **CELLS["products-sage.clique2x2"], routed_alpha=1.0))


@pytest.mark.parametrize("buckets", ["full", "capped"])
def test_the_route_plan_sorts_and_never_gathers(
        programs, capped_clique, buckets):
    """The plan carries the ids as a sort's payload and cuts its buckets
    out as slices: no gather, no ``while`` (``searchsorted``'s) and at most
    two sorts (the plan's, and the overflow compaction's where a bucket can
    overflow) under ``route_plan``; the rows are gathered twice under
    ``tier_hot``, by their owner and by the requester, and un-bucketing
    sorts nothing."""
    program = (programs["products-sage.clique2x2"] if buckets == "full"
               else capped_clique)
    hot = [(op, path) for op, path in program
           if path.startswith("feature_gather/tier_hot/")]
    plan = [(op, path) for op, path in hot
            if path.startswith("feature_gather/tier_hot/route_plan/")]
    assert plan
    gathers = [path for op, path in plan
               if op == "gather" or path.endswith("/gather")]
    assert not gathers, gathers[:5]
    loops = [path for op, path in plan
             if op == "while" or "searchsorted" in path or "while" in path]
    assert not loops, loops[:5]
    sorts = [path for op, path in plan if op == "sort"]
    assert 1 <= len(sorts) <= (1 if buckets == "full" else 2), sorts
    rows = [path for op, path in hot
            if op == "gather" and path.endswith("/gather")
            and "route_fallback" not in path]
    assert 1 <= len(rows) <= 2, rows
    assert [path for op, path in hot if op == "sort"] == sorts


def test_the_attention_scatters_only_in_its_backward(programs):
    """The dense fanout path's forward pass scatters nothing: the self lane
    is an operand of the max, the denominator and the sum, not a lane
    scattered in. The backward's one kind of scatter is that of the rows
    named by more than one lane, inside the loop of the transposed lane
    gather (``layers.gather_lane_rows``, ROADMAP S5)."""
    scatters = [path for op, path in programs["products-gat.hbm"]
                if op == "scatter"]
    assert not [p for p in scatters if p.startswith("jvp(GAT)")]
    assert any("attn_aggregate" in p for p in scatters)


GAT_LAYERS = range(len(CELLS["products-gat.hbm"]["fanout"]))


def test_the_attention_moves_rows_once_a_layer_and_logits_never(programs):
    """On the dense fanout path a lane's logit is built from operands that
    are in lane order already (the source half read off the gathered ``z``
    row, the target half broadcast over the fanout axis): nothing is
    gathered or scattered under ``attn_logits``, forward or transposed,
    and a layer's one forward gather is that of its rows, under
    ``attn_aggregate``."""
    program = programs["products-gat.hbm"]
    logits = re.compile(r"^(transpose\()?jvp\(GAT\)\)?/conv\d+/attn_logits(/|$)")
    moved = [(op, path) for op, path in program if logits.match(path)
             and (op in ("gather", "scatter")
                  or path.endswith(("/gather", "/scatter-add")))]
    assert not moved, moved[:5]
    for l in GAT_LAYERS:
        under = [path for o, path in program
                 if o == "gather" and path.startswith(f"jvp(GAT)/conv{l}/")]
        assert under == [f"jvp(GAT)/conv{l}/attn_aggregate/gather"], under


@pytest.mark.parametrize("layer", list(GAT_LAYERS))
def test_the_transposed_row_gather_is_a_gather(programs, layer):
    """The transpose of a layer's row gather (``layers.gather_lane_rows``'s
    rule, which enters no scope of its own) keeps the path of the forward
    call, so ``attn_aggregate_device_ms``, ``backward_device_ms`` and
    ``attn_roofline``'s time go on counting it: every gather, scatter, sort
    and loop of the layer's backward lies under
    ``transpose(jvp(GAT))/conv{l}/attn_aggregate/``. There it is the two
    sorts of the lanes' plan (the probe chose the plan of two sorts over
    lanes and one query a row, which makes a row's lane without the 4-byte
    scatter ISSUE 35 sketched: PERF.md, PR 35), ONE gather of ``(rows, H,
    F)`` outside the loop, and one ``while`` whose body gathers and
    scatter-adds the repeats' chunk: no scatter of rows stands outside a
    ``while`` body, so what is scattered is the lanes that repeat a row and
    not the lanes of the block."""
    program = programs["products-gat.hbm"]
    home = f"transpose(jvp(GAT))/conv{layer}/attn_aggregate/"
    moving = [(op, path) for op, path in program
              if op in ("gather", "scatter", "sort", "while")
              and f"jvp(GAT))/conv{layer}/" in path]
    assert moving and all(path.startswith(home) for _, path in moving), moving
    kinds = lambda op: [path[len(home):] for o, path in moving if o == op]
    assert kinds("sort") == ["sort", "sort"]
    assert kinds("while") == ["while"]
    assert sorted(kinds("gather")) == ["gather", "while/body/gather"]
    assert kinds("scatter") == ["while/body/scatter-add"]
    # nothing of the rule outside the five scopes' paths
    assert not [path for _, path in program
                if "GAT" in path and "/while" in path
                and "/attn_aggregate/while" not in path]


def test_the_overflow_fallback_has_its_scope(capped_clique):
    """``route_fallback`` exists only where a bucket can overflow."""
    paths = paths_of(capped_clique)
    assert has(paths, r"^feature_gather/tier_hot/route_fallback/")
    rx = re.compile(
        spec.load_metric("gather_route_device_ms")["args"]["pattern"])
    assert any(rx.search(p) for p in paths if "route_fallback" in p)


def test_the_reindex_has_the_three_phases():
    def run(seeds, nbr):
        with trace.trace_scope("reindex_layer_0"):
            return reindex_layer(seeds, jnp.int32(6), nbr, 16)

    text = jax.jit(run).lower(
        jnp.arange(8, dtype=jnp.int32),
        jnp.arange(24, dtype=jnp.int32).reshape(8, 3) % 11,
    ).compile().as_text()
    paths = _OP_NAME.findall(text)
    for phase in ("dedup", "compact", "relabel"):
        assert has(paths, rf"reindex_layer_0/{phase}/"), phase


# -- the step keeps the sampler's counts --------------------------------------


def block_counts(trainer, seeds, key):
    """Per-hop valid edges and unclipped frontier sizes, seeds outward, of
    the block ``step(seeds, key)`` draws for its one worker."""
    padded = np.full(BATCH, -1, np.int32)
    padded[:len(seeds)] = seeds
    sample_key = jax.random.split(jax.random.fold_in(jnp.asarray(key), 0))[0]
    _, _, adjs, overflow, _, frontier = trainer.sampler.sample_padded(
        trainer.sampler.topo, jnp.asarray(padded), jnp.int32(len(seeds)),
        sample_key)
    edges = [int((np.asarray(a.edge_index)[0] >= 0).sum()) for a in adjs]
    return edges[::-1], [int(f) for f in frontier][::-1], int(overflow)


def test_sample_edges_are_the_valid_edges_of_the_blocks():
    trainer, params, opt_state, labels = build([3, 2], [32, 64])
    key = np.asarray(jax.random.PRNGKey(5))
    seeds = np.arange(BATCH)
    trainer.step(params, opt_state, seeds, labels, key)
    edges, frontier, overflow = block_counts(trainer, seeds, key)
    value = trainer.metrics.value
    assert np.asarray(value("sample.edges")).tolist() == edges
    assert np.asarray(value("sample.frontier")).tolist() == frontier
    assert int(value("sample.frontier_overflow")) == overflow == 0
    assert "sample.edges" in trainer.metrics_report()


def test_caps_too_small_are_reported_on_a_replicated_topology():
    caps = [10, 12]
    trainer, params, opt_state, labels = build([3, 2], caps)
    trainer.step(params, opt_state, np.arange(BATCH), labels,
                 np.asarray(jax.random.PRNGKey(5)))
    frontier = np.asarray(trainer.metrics.value("sample.frontier"))
    dropped = int(trainer.metrics.value("sample.frontier_overflow"))
    assert dropped > 0
    assert (frontier > np.asarray(caps)).any()
    assert dropped == int(np.maximum(frontier - np.asarray(caps), 0).sum())


def test_epoch_scan_stacks_the_counts_per_step():
    trainer, params, opt_state, labels = build([3, 2], [32, 64])
    seed_mat = trainer.pack_epoch(np.arange(3 * BATCH), seed=0)
    trainer.epoch_scan(params, opt_state, seed_mat, labels,
                       jax.random.PRNGKey(2))
    assert np.shape(trainer.metrics.value("sample.edges")) == (3, 2)
    assert np.shape(trainer.metrics.value("sample.frontier")) == (3, 2)
    assert np.shape(trainer.metrics.value("sample.frontier_overflow")) == (3,)
    assert (np.asarray(trainer.metrics.value("sample.edges")) > 0).all()
