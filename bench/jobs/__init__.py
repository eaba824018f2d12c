"""Job kinds: one module per kind, found by the ``kind`` of a workload file.

A module defines ``setup(config, traffic, seed)``, which makes the inputs from
the seed, builds the library's state as the configuration states it, and
returns a job object with these methods:

- ``run()``: one whole job through the library's public entry points, ending
  when its outputs are ready; returns a record (a dict) of what it produced;
- ``loads()``: the library's counters (``ArrayContext.loads()``);
- ``counts(record)``: the operations and bytes the algorithm needs for that
  job, from its shapes, for the roofline shares;
- ``collect()``: after the window, reads the outputs that are compared to
  the host and frees the library's state;
- ``check(records)``: runs the plain reference and returns the compared
  numbers (``harness.Check``) and how many of the jobs failed;
- ``control()``: the compared numbers of the control, the reference computed
  in the precision below the configuration's, on the same inputs
  (``bench/limits.py`` reads it; benchmark runs do not).
"""
