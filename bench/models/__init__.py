"""Client model families.  A configuration's ``model`` block names its
family (``"family": "cnn"``), and the harness loads ``bench/models/<family>.py``
by path, as it loads a metric's reader: a new family is one new file, and no
list names the families.

A family module defines, with ``model`` the configuration's ``model`` block:

* ``make_frozen(seed, model)``, optional: weights the clients do not train,
  made on the device from ``seed`` in one jitted call (counted in
  ``setup_s``).  The program's backend and the reference are handed the same
  arrays, so the reference never reimplements the program's initialisation
  of them.  Without it, ``frozen`` is ``None`` wherever it is passed.
* ``program_backend(model, frozen)``: the program's backend for the cell's
  entry point (the system under test).
* ``reference_model(model, numerics, lr, frozen)``: the plain reference
  model, at ``numerics`` (``bench.reference.REFERENCE`` or ``CONTROL``),
  with jitted ``init(key)``-made parameters as a flat dict of leaves,
  ``predict(p, x)``, ``probe(p, xs)`` (every client's probe batch, each
  client's Eq. 5 feature), ``sgd_step(p, fsum, xs, ys, step)`` (one SGD step,
  and the Eq. 6 feature sum of its batch added to ``fsum``) and
  ``feature_dim``.  It imports nothing of the program.
* ``to_reference(params)``: the program's message tree as the flat dict of
  leaves that the reference's ``init`` makes and ``bench/compare.py``
  reads; applied leaf by leaf, so it also takes the client-stacked
  messages.
* ``make_dataset(seed, data, model, sharding=None)``: the federated dataset
  (``images``, ``labels``, ``test_images``, ``test_labels``) made on the
  device from ``seed`` in one jitted call; ``sharding`` places the client
  axis.  ``num_classes(model)``: the labels' classes, for the macro-F1.
* ``train_flops(model)``, ``forward_flops(model)``, ``feature_flops(model)``:
  operations per item of one SGD step, of one full forward (the eval), and
  of the Eq. 5/6 feature forward (a family whose feature stops at an
  intermediate layer counts less than its full forward).
  ``bench/flops.py`` counts the items.
* ``NUMBERS`` and ``numbers(prog, ref, data)``, optional: further numbers
  of the comparison, by name, that a cell's limits may hold
  (``bench/compare.py`` merges them in).
* ``check_model(model)``, optional: raises where the configuration is not
  the model the program builds (a test calls it).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from types import ModuleType


def load_module(path: Path, name: str) -> ModuleType:
    """The Python file ``path``, loaded by path as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(bench: Path, model: dict) -> ModuleType:
    """``<bench>/models/<family>.py`` of a configuration's ``model`` block."""
    where = f"{bench.name}/models/"
    family = model.get("family")
    if not family:
        raise ValueError(f"the configuration's model names no family: set model.family "
                         f"to the name of a module in {where}")
    path = bench / "models" / f"{family}.py"
    if not path.is_file():
        raise FileNotFoundError(f"model family {family!r} has no module: {where}{family}.py "
                                "is missing")
    return load_module(path, f"bench_model_{family}")
