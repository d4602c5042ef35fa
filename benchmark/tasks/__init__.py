"""One module per training task, found by the ``"task"`` key of a
configuration's file.

A task module is what ``harness.run_cell``, ``harness.first_batches``
and ``control.py`` call, and nothing else:

* ``spec_mismatches(cfg, spec, hyper) -> list[str]``: where the file's
  ``model``/``optimizer`` blocks and the program's finalized config
  differ.
* ``build_loader(cell, cfg, seed, logdir) -> (loader, rows_per_step)``:
  ``loader.batches(n)`` and ``loader.health``, over the cell's mix read
  through the task's own generator.
* ``first_moment(opt_state) -> pytree``: what the optimizer holds of the
  first gradient after one step.
* ``reference_steps(spec, hyper, seed, batches, **kw) -> dict``: the
  plain reference's ``loss``, ``terms``, ``grad_norm``,
  ``first_trace_norm``, ``delta_norm``; ``**kw`` carries the control's
  ``precision`` and the fault's ``rows``.
* ``extra_numbers(program, reference) -> dict``: numbers of the task's
  own that ``compare.numbers`` puts beside the general ones.
* ``train_ops_per_row(spec) -> float``: operations forward and backward
  REQUIRE for one row of the batch.
"""
