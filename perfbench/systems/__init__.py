"""One module per kind of configuration (a config file's ``"system"``).

A system module brings everything about its kind, and the harness and the
tests find it by that name alone, so a new kind joins by a new module here
and files of its own (config, traffic, limits), never by an edit to a
file that is already here. It gives the names in ``CONTRACT``:

* ``context(config)`` — the numerics the configuration states, entered for
  the cell's whole life;
* ``build(config, traffic, *, seed, seconds, tracer, engine=True)`` — the
  cell (below); ``engine=False`` builds no program, for the reference and
  the calibration of limits alone;
* ``VARIANTS`` — what ``cell.reference(variant)`` can put in the program's
  place for ``calibrate.py``: the control first, then the faults;
* ``TINY``, ``TINY_TRAFFIC`` — keys over the configuration's and the
  traffic's that cut a cell to a size the tests run on the CPU.

The cell that ``build`` returns has:

* ``engine`` — the program under test, a ``repro.ps.PSEngine``;
* ``run_round(r)`` — the window's one call, ``engine.run(until_round=r+1)``;
* ``work`` — what one round does: ``tokens`` and ``worker_steps``;
* ``counts`` — operations and bytes the counts in ``perfbench.counts``
  give for this cell's shapes, for the per-layer readers;
* ``devices`` — the devices the program runs on;
* ``free()`` — drop the program's state;
* ``reference(variant)`` — the plain reference's readings over the first
  rounds (``perfbench.reference.adaseg.readings``); ``variant`` is None
  for the check, or one of ``VARIANTS``.

A language-model system reuses ``lm.LMCell`` and passes its own three
model-specific parts: ``arch(config)``, its ``reference`` module
(``init_params``, ``loss``) and ``counts(config, seq)``. The tests' faults
(``tests/tiny_cells.plant``) patch ``repro.models.transformer.loss_fn``,
so its oracle has to call that through the module, as ``LMCell`` does.
"""

CONTRACT = ("context", "build", "VARIANTS", "TINY", "TINY_TRAFFIC")
