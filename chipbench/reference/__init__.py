"""The plain float32 reference of the training step: ``graph.py`` is the
sampler's contract, every other module the plain side of the model of that
name, found by a configuration's ``model`` key. It imports nothing of
``quiver_tpu`` and is handed nothing that the program has made but the
sampled blocks, which it first checks against its own copy of the graph."""
