"""Drivers: what a run does in its window, and how it is judged.

A traffic mix names its driver (``"driver": "serve"``), and the harness
loads ``bench/drivers/<driver>.py`` by that name, so a cell of a new kind
(a training run, say) arrives as new files.  A driver module exports
``Driver(spec, seed, seconds)`` with:

* ``setup()`` — weights from the seed, the system built, every shape of the
  window warmed;
* ``window() -> (t0, t1)`` — drives the system for ``seconds`` on the host
  clock;
* ``drain()`` — finishes what the window started that is to be judged;
* ``tally() -> (attempted, failed)``;
* ``compare(control, release=True) -> dict`` — frees the system's device
  state (unless ``release`` is false) and reads the timed path's output
  against the plain reference; with ``control``, also the control's;
* ``checks(compared, control) -> {name: {"value", "limit"}}`` — the numbers
  ``correct`` is decided on; with ``control``, the control's reading stands
  in the program's place.
"""
