"""The plain float32 reference of the training step. It imports nothing of
``quiver_tpu`` and is handed nothing that the program has made but the
sampled blocks, which it first checks against its own copy of the graph."""
