"""The graphs a configuration may name: one module per generator, found by
the configuration's ``graph.generator`` key (``spec.load_graph``). A graph
module holds ``make(cfg, seed)``, which draws ``inputs.Inputs`` (the CSR, the
rows in the configuration's dtype, the labels, who may be a seed, what an
edge carries) from the seed at the configuration's shapes, and
``describe(cfg)``, the same shapes with no values (``rehearse.py``); it may
hold ``lane_faults(data, seeds, block)``, the check of what a sampled lane
carries against ``Inputs.edge_data`` (``check.compare`` adds its counts to
``block_faults``). It imports nothing of ``quiver_tpu``."""
