"""One module per kind of configuration (a config file's ``"system"``).

Each module gives ``context(config)`` (the numerics the configuration
states, entered for the cell's whole life) and ``build(config, traffic, *,
seed, seconds, tracer)``, which returns an object with:

* ``engine`` — the program under test, a ``repro.ps.PSEngine``;
* ``run_round(r)`` — the window's one call, ``engine.run(until_round=r+1)``;
* ``work`` — what one round does: ``tokens`` and ``worker_steps``;
* ``counts`` — operations and bytes the counts in ``perfbench.counts``
  give for this cell's shapes, for the per-layer readers;
* ``free()`` — drop the program's state;
* ``reference(variant)`` — the plain reference's readings over the first
  rounds (``perfbench.reference.adaseg.readings``); ``variant`` is None
  for the check, or a control or fault for the calibration of limits.
"""
