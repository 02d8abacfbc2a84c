"""Regenerate docs/API.md — the public-surface index.

Walks each (module, title) pair below, imports it on the CPU backend, and
tables every ``__all__`` export with the first line of its docstring.
Run after adding/renaming exports:

    JAX_PLATFORMS=cpu python scripts/gen_api_md.py
"""

import importlib
import inspect
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SECTIONS = [
    ("quiver_tpu", "Package root (reference: quiver/__init__.py exports)"),
    ("quiver_tpu.core.topology", "Graph topology (CSRTopo, device placement)"),
    ("quiver_tpu.core.sharded_topology",
     "Mesh-sharded topology (CSR partitioned across chips)"),
    ("quiver_tpu.core.hetero_sharded",
     "Mesh-sharded heterogeneous topology (per-relation partitions)"),
    ("quiver_tpu.core.config", "Config enums + byte-size parser"),
    ("quiver_tpu.core.memory", "Device/host memory placement"),
    ("quiver_tpu.sampling", "Public sampling surface (the sampler family)"),
    ("quiver_tpu.sampling.sampler", "GraphSageSampler (homo)"),
    ("quiver_tpu.sampling.dist",
     "Distributed sampler over a mesh-sharded topology"),
    ("quiver_tpu.sampling.hetero", "Heterogeneous sampler"),
    ("quiver_tpu.sampling.dist_hetero",
     "Distributed heterogeneous sampler (shared route plan per hop/type)"),
    ("quiver_tpu.sampling.saint", "GraphSAINT samplers"),
    ("quiver_tpu.feature.feature", "Tiered feature store"),
    ("quiver_tpu.feature.shard", "Mesh-sharded feature store"),
    ("quiver_tpu.models", "Model families + layer-wise inference"),
    ("quiver_tpu.parallel.mesh", "Device mesh / clique topology"),
    ("quiver_tpu.parallel.routing",
     "Capped-bucket owner routing (shared comm core)"),
    ("quiver_tpu.parallel.trainer", "Distributed fused trainer"),
    ("quiver_tpu.parallel.train", "Single-chip train step helpers"),
    ("quiver_tpu.parallel.pipeline",
     "Prefetcher + pipelined-epoch batch container"),
    ("quiver_tpu.resilience",
     "Fault tolerance — non-finite step guard, fault injection"),
    ("quiver_tpu.resilience.elastic",
     "Elastic mesh resilience — cross-mesh resume, circuit breaker"),
    ("quiver_tpu.resilience.integrity",
     "Checkpoint integrity — manifest schema, checksums, verification"),
    ("quiver_tpu.streaming",
     "Transactional streaming graph mutation — delta ingestion, atomic "
     "commits, versioned invalidation"),
    ("quiver_tpu.serving",
     "Online inference serving — deadline-aware micro-batching over "
     "AOT-compiled ladder programs"),
    ("quiver_tpu.serving.aot",
     "Persisted AOT executables — fingerprint-keyed disk cache for "
     "compile-free cold start"),
    ("quiver_tpu.serving.fleet",
     "Serving fleet — replica scale-out over one shared executable "
     "cache with SLO-class admission control"),
    ("quiver_tpu.control",
     "quiver-ctl — telemetry-driven cache & routing control plane"),
    ("quiver_tpu.ooc",
     "quiver-ooc — out-of-core disk tier: raw mmap-native format, "
     "disk-backed feature store, async window staging"),
    ("quiver_tpu.ops.sample", "Sampling ops (XLA)"),
    ("quiver_tpu.ops.reindex", "Dedup/reindex"),
    ("quiver_tpu.models.layers", "Message-passing primitives"),
    ("quiver_tpu.ops.pallas.sample", "Pallas windowed sampler"),
    ("quiver_tpu.utils.reorder", "Degree-based feature reorder"),
    ("quiver_tpu.utils.checkpoint",
     "Atomic manifest checkpointing (integrity-verified)"),
    ("quiver_tpu.utils.trace", "Tracing/profiling scopes"),
    ("quiver_tpu.obs",
     "graftscope — metrics registry, step timeline, exporters"),
    ("quiver_tpu.obs.tracing",
     "grafttrace — causal spans + Chrome trace-event export"),
    ("quiver_tpu.obs.recorder",
     "grafttrace — black-box flight recorder, postmortem bundles"),
    ("quiver_tpu.obs.endpoint",
     "grafttrace — live telemetry HTTP endpoint"),
    ("quiver_tpu.datasets", "Dataset loaders + planted graphs"),
    ("quiver_tpu.tools.lint",
     "graftlint static analyzer (trace-safety rules)"),
    ("quiver_tpu.tools.audit",
     "graftaudit — jaxpr/HLO program auditor (lowered-IR invariants)"),
    ("quiver_tpu.tools.audit.mem",
     "graftmem — static per-device memory & layout accounting"),
    ("quiver_tpu.tools.sarif",
     "Shared SARIF plumbing (lint + audit, merged CI artifact)"),
]


def first_line(obj) -> str:
    doc = inspect.getdoc(obj) or ""
    line = doc.splitlines()[0].strip() if doc else ""
    # flax dataclass reprs embed object addresses — strip them so regens
    # are deterministic and diffs stay reviewable
    line = re.sub(r" object at 0x[0-9a-fA-F]+", " object", line)
    return line.replace("|", "\\|")


def main():
    out = [
        "# API index",
        "",
        "Auto-generated (`JAX_PLATFORMS=cpu python scripts/gen_api_md.py`); "
        "regenerate after adding exports.",
        "Public surface by module — first docstring line for each export.",
    ]
    for modname, title in SECTIONS:
        mod = importlib.import_module(modname)
        names = sorted(getattr(mod, "__all__", []))
        out += ["", f"## `{modname}` — {title}", "",
                "| Export | Summary |", "|---|---|"]
        for n in names:
            obj = getattr(mod, n, None)
            out.append(f"| `{n}` | {first_line(obj)} |")
    path = os.path.join(REPO, "docs", "API.md")
    with open(path, "w") as fh:
        fh.write("\n".join(out) + "\n")
    print(f"wrote {path}: {len(SECTIONS)} sections")


if __name__ == "__main__":
    main()
