"""The program's assemblies a configuration may name: one module per
assembly, found by the configuration's ``assembly`` key
(``spec.load_assembly``). With ``adapter.py`` and the model files
(``models/``) these are the only modules here that import ``quiver_tpu``. An
assembly module holds ``build(cfg, traffic, data, mesh)``, which calls the
program's public constructors as a user does and returns the parts that
``DistributedTrainer`` takes (``.sampler``, ``.feature``, and what else its
``blocks`` needs), and ``blocks(parts, cfg, seeds, key, workers)``, which
draws again, outside the step, the ``reference.graph.Block`` of each worker
that ``step(seeds, key)`` trains on. The keys of a traffic mix that say
where the topology and the rows live are the assembly's to read, and to
refuse where it does not know them."""
