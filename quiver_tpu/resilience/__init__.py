"""Resilience layer — fault-tolerant training over the fused SPMD stack.

The reference ships zero fault tolerance (SURVEY §5: ``torch.save`` only
for preprocessing artifacts; a failed worker kills the ``mp.spawn`` run).
Long-running production training — the ROADMAP north star, and the
operating regime GNNSampler-style deployments assume (PAPERS.md, arxiv
2108.11571) — needs three distinct defenses, each living at the layer
where its fault class occurs:

* **In-program non-finite step guard** (``guard.py``): a NaN/Inf loss or
  gradient inside the compiled train step must not poison params.
  :func:`guard_verdict` counts non-finite values per worker and psums the
  verdict mesh-wide so every chip agrees; :func:`guarded_update`
  cond-skips the optimizer update (params/opt_state pass through
  bit-unchanged). Wired into ``DistributedTrainer(nonfinite_guard=True)``
  with skip/non-finite counters on the graftscope registry.
* **Checkpoint / auto-resume** (``parallel/trainer.py`` +
  ``utils/checkpoint.py``): ``DistributedTrainer(checkpoint_dir=,
  checkpoint_every=)`` saves (params, opt_state, step, PRNG key)
  asynchronously between scan chunks; :meth:`DistributedTrainer.resume`
  restores the latest state and the caller replays the packed seed
  stream from the saved step — the resumed loss trajectory is
  bit-identical to an uninterrupted run (tests/test_resilience.py).
* **Retrying prefetcher** (``parallel/pipeline.py``): host-side
  sample/gather/transform failures are transient (preempted host,
  flaky storage) — ``Prefetcher(retries=, backoff=, skip_policy=)``
  retries with exponential backoff + deterministic jitter and can
  skip-and-count a poisoned batch after retries exhaust.

The elastic layer (PR 7) extends the same defenses to faults that CHANGE
the world instead of leaving it intact:

* **Topology-portable, integrity-verified checkpoints**
  (``integrity.py`` + ``utils/checkpoint.py``): saves are atomic (temp
  dir + ``COMMIT`` marker + one rename) with a mesh-agnostic manifest of
  per-array checksums; restore quarantines corrupt/partial checkpoints
  and falls back to the newest valid one.
* **Elastic resume** (``elastic.py`` + ``DistributedTrainer(
  logical_workers=)`` / ``resume(mesh=)``): a run checkpointed at F=8
  continues at F=4 — :func:`worker_ordered_mean` makes the step reduction
  bitwise mesh-shape independent, and the sharded topology / three-tier
  feature store re-partition via their ``replan`` seams.
* **Degraded-mode feature serving** (``elastic.py``):
  :class:`CircuitBreaker` + :class:`DegradedFeature` turn a cold-tier
  OUTAGE into fallback rows (zeros/last-good) and a
  ``resilience.degraded_lookups`` counter instead of a dead epoch.

``faults.py`` is the test substrate proving all of the above: a seeded,
fully deterministic :class:`FaultPlan` that injects NaN rows into gathered
features (in-program, step-indexed), transient exceptions into host
sampler/feature lookups, and simulated preemption — reusable as a chaos
lane by benchmarks (``benchmarks/chaos.py``).
"""

from .elastic import (
    CircuitBreaker,
    DegradedFeature,
    validate_resume_meta,
    worker_ordered_mean,
)
from .faults import (
    FaultPlan,
    FaultyFeature,
    FaultySampler,
    Preemption,
    TransientFault,
)
from .guard import guard_verdict, guarded_update, nonfinite_count
from .integrity import CorruptCheckpoint

__all__ = [
    "CircuitBreaker",
    "CorruptCheckpoint",
    "DegradedFeature",
    "FaultPlan",
    "FaultySampler",
    "FaultyFeature",
    "Preemption",
    "TransientFault",
    "guard_verdict",
    "guarded_update",
    "nonfinite_count",
    "validate_resume_meta",
    "worker_ordered_mean",
]
