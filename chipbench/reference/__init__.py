"""Plain float32 reference of the cells' training steps (see
``model.py``, ``select.py`` and ``train.py``). It imports nothing of the
program."""
