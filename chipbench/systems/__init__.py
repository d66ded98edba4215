"""The systems the benchmark can run, one file each: ``systems/<name>.py``.

A configuration file names its system under ``"system"`` (``"tnn"`` when it
has none); ``harness/spec.find_cell`` loads that file by name and puts the
module on the cell as ``cell.system``. The harness reaches the system only
through it, so a new system is a new file here with its configurations,
traffic mixes and metric readers, and no edit to a file that is there.

A system module gives:

  pool(config, seed)
      The request inputs, made from the seed (numpy on the host). Every
      request reads a run of them.
  payload(pool, offset, length)
      One request's input: what ``engine.submit`` takes. ``offset`` and
      ``length`` come from the traffic schedule (``harness/traffic.py``);
      ``length`` is the request's count of units (volleys, rows, tokens).
  init_params(key, config)
      The seeded parameters, from a ``jax.random.PRNGKey``. The program and
      the reference get the same ones.
  engine(params, config, cell)
      The program under test, built for the cell called ``cell``. It has
      ``submit(payload) -> handle``, ``step() -> retired handles``,
      ``has_work``, ``n_volleys`` (its count of units served), ``close()``
      (waits for its background work), ``n_snapshots`` and
      ``final_state()`` (what it learned, such as its weights, or None). A
      handle has ``req_id``, ``outputs`` (a list that grows by one entry per
      unit served) and ``result()``.
  control_engine(params, config)
      The reference in the program's place, one precision below the one the
      configuration states, with the engine's interface: the control that
      the comparison has to fail.
  stateful(config)
      Whether an output depends on what else was served (learning, a carry
      across units). The window then records which units every step served.
  compare(config, pool, params_host, requests, served, final_state)
      The readings, by name, that ``harness/check.held`` judges against
      ``config["check"]["limits"]``. ``params_host`` is ``init_params``'s
      result on the host, ``requests`` the window's ``loop.Request``s, and
      ``served`` what each step served (empty unless ``stateful``). It runs
      after the program's state is freed.

The runner warms a cell up with the cell's own traffic: a closed loop's
first steps, or for an open loop ``config["n_slots"]`` requests of two units
each, served to the end. Shapes that traffic cannot reach are for the
system's ``engine`` to warm up when it is built (counted in ``setup_s``).
"""
