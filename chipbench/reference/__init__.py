"""Plain float32 reference of the cells' training steps (see
``model.py``, the block files in ``blocks/``, ``select.py`` and
``train.py``). It imports nothing of the program."""
