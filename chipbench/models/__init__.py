"""The program's side of each model a configuration may name: one module
per model, found by the configuration's ``model`` key (``spec.load_model``).
With ``adapter.py`` these are the only modules here that import
``quiver_tpu``. A model module holds ``SCOPE``, ``build(cfg)``,
``to_program_tree(weights)`` and ``from_program_tree(tree, layers)``; its
plain side is ``reference/<the same name>.py``."""
