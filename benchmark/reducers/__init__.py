"""Reducers of kinds ``reduce.py`` does not have: one file each, found by
the name a ``layer_metrics/<metric>.json`` gives, with a
``reduce(facts, **args)`` that returns ``None`` when it finds nothing to
read (``benchmark/reduce.py``, ``run_reducer``)."""
