"""The chip benchmark's harness: the yardstick that later changes may not edit.

Modules, from the entry point down:

  spec       finds a cell and the files it names (configs/, systems/, traffic/,
             metrics/)
  traffic    the one general traffic generator: GRF volley pool and schedules
  grf        numpy copy of the GRF front end that encodes the volleys
  system     the TNN engines: the only module that imports the program under
             test (bound with traffic, reference and check by systems/tnn.py)
  loop       drives the program's submit/step loop through the measured window
  reference  plain jax.numpy TNN (forward, 1-WTA, STDP), independent of the program
  check      the comparison that decides ``correct``
  spans      the program's host spans in the trace: idle time split by phase
  trace      reduction of a profiler trace to device busy time, kernel time, gaps
  work       operations and bytes the RNL algorithm needs, from the shapes
  peaks      published chip peaks keyed by ``device_kind``
  runner     one run of one cell: set-up, window, metrics, check, result line
"""
