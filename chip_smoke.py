"""Bring-up smoke run: the serve paths end to end on a TPU.

Drives the system's main path once, through the entry points a user
calls, with everything generated from ``--seed``:

  tnn     TNN inference at deployment width: a few hundred client streams
          of GRF-encoded volleys through ``TNNEngine`` (128 slots,
          ``backend="auto"``) over a 2-layer Catwalk network (n=64
          dendrites, k=2, w_max=7, T=16). Every retired stream must be
          bit-exact against ``tnn_engine.reference_outputs`` on the
          ``closed_form`` engine, and every step must have run ``pallas``
          (compiled by Mosaic, not interpreted).
  learn   the same network with a recurrent layer 2, served with
          learn-while-serve and a snapshot; each step's outputs must equal
          the learning-off engine's at the same pre-update weights.
  lm      ``internlm2-1.8b`` at its published widths (random weights from
          the seed) through ``serve.Engine``: greedy tokens of a batched,
          slot-refilling serve must equal each request served alone
          through the same slot count.

``--four-chips`` runs only the 4-chip phase: the ``tnn`` network served by
``TNNEngine(mesh=specs.tnn_mesh(n_column=4), backend="pallas")`` (the
``kernels/rnl_shard.py`` shard_map path), bit-exact against the
single-device engine in the same process, with the layer weights spread
over all 4 devices.

Per-phase detail goes to stdout; the last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
The script exits non-zero (and prints no result) when JAX finds no TPU,
when a phase raises, or when a comparison differs.

Run from the repository root:  python chip_smoke.py [--seed 0] [--four-chips]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

import jax

N_FEATURES = 2048      # analog features per volley
N_FIELDS = 8           # GRF fields per feature -> 16384 input lines
T_STEPS = 16           # gamma-cycle length
GRF_CUTOFF = 0.5       # activations below this stay silent
LM_ARCH = "internlm2-1.8b"


def _log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _tnn_network(recurrent: bool = False, backend: str = "auto"):
    """2-layer Catwalk TNN: 256 columns x rf 64 x 16 neurons over the
    16384 GRF lines, then 16 columns x rf 256 x 16 neurons."""
    from repro.core import layer, network

    l1 = layer.TNNLayer(n_columns=256, rf_size=64, n_neurons=16,
                        threshold=20, t_steps=T_STEPS, dendrite="catwalk",
                        k=2, w_max=7, backend=backend)
    l2 = layer.TNNLayer(n_columns=16, rf_size=256, n_neurons=16,
                        threshold=12, t_steps=T_STEPS, dendrite="catwalk",
                        k=2, w_max=7, backend=backend, recurrent=recurrent)
    return network.make_network([l1, l2])


def _client_streams(rng, n_clients: int):
    """``n_clients`` streams of 1-8 GRF-encoded volleys, encoded in bulk."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import coding

    lengths = rng.integers(1, 9, size=n_clients)
    feats = rng.random((int(lengths.sum()), N_FEATURES), dtype=np.float32)
    enc = coding.grf_encode(jnp.asarray(feats), N_FIELDS, T_STEPS,
                            cutoff=GRF_CUTOFF)
    volleys = np.asarray(enc).reshape(len(feats), -1)
    return np.split(volleys, np.cumsum(lengths)[:-1])


def _serve(eng, streams):
    """Submit, time the first step (compile) and the drain; requests back
    in submission order."""
    reqs = [eng.submit(s) for s in streams]
    t0 = time.perf_counter()
    eng.step()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.run()
    return reqs, first, time.perf_counter() - t0


def _check_lowered_kernel(eng, batch) -> None:
    """The jitted step ``TNNEngine.step`` ran for ``pallas`` lowers to
    Mosaic kernels, not the interpreter."""
    no_carry = (None,) * len(eng.net.layers)
    text = eng._fwd.lower(
        eng.params, eng._place(batch), eng._place_carry(no_carry)).as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("lowered TNN step holds no tpu_custom_call")


def _spread(reqs) -> str:
    """Share of output lines that spiked, over every retired volley."""
    import numpy as np

    from repro.core import coding

    outs = np.concatenate([r.result().reshape(r.n_cycles, -1)
                           for r in reqs])
    return f"{float(np.mean(outs != int(coding.NO_SPIKE))):.4f}"


def phase_tnn(seed: int) -> None:
    import numpy as np

    from repro.core import network, policy
    from repro.serve import tnn_engine

    net = _tnn_network()
    params = network.init_network(jax.random.PRNGKey(seed), net)
    streams = _client_streams(np.random.default_rng(seed), n_clients=300)
    n_volleys = sum(len(s) for s in streams)
    _log("tnn", f"layers C x rf x Q: "
         + ", ".join(f"{lc.n_columns}x{lc.rf_size}x{lc.n_neurons}"
                     for lc in net.layers)
         + f"; inputs {net.n_inputs}; {len(streams)} streams, "
         f"{n_volleys} volleys; density "
         f"{np.mean(np.concatenate(streams) < T_STEPS):.4f}")
    per_layer = [policy.resolve_engine("auto", lc.n_columns)
                 for lc in net.layers]
    _log("tnn", f"engine per layer (auto): {per_layer}")
    if per_layer != ["pallas"] * len(net.layers):
        raise AssertionError(f"auto did not resolve to pallas: {per_layer}")

    eng = tnn_engine.TNNEngine(
        params, net, tnn_engine.TNNServeConfig(n_slots=128, backend="auto"))
    reqs, first_s, drain_s = _serve(eng, streams)
    _check_lowered_kernel(eng, np.concatenate(streams)[:128])
    st = eng.stats()
    _log("tnn", f"steps {int(st['n_steps'])}, engine {eng.engine}, first "
         f"step (incl. compile) {first_s:.2f} s, remaining steps "
         f"{drain_s:.2f} s; output spike share {_spread(reqs)}")
    if eng.engine != "pallas":
        raise AssertionError(f"the engine resolved to {eng.engine}")

    ref_net = network.make_network(
        [dataclasses.replace(lc, backend="closed_form") for lc in net.layers])
    t0 = time.perf_counter()
    bad = [i for i, (s, r) in enumerate(zip(streams, reqs))
           if not np.array_equal(
               tnn_engine.reference_outputs(params, ref_net, s), r.result())]
    _log("tnn", f"closed_form reference {time.perf_counter() - t0:.2f} s; "
         f"mismatched streams {len(bad)}/{len(streams)}")
    if bad:
        raise AssertionError(f"streams {bad[:10]} differ from the reference")


def phase_learn(seed: int) -> None:
    import numpy as np

    from repro.core import network
    from repro.serve import tnn_engine

    net = _tnn_network(recurrent=True)
    params = network.init_network(jax.random.PRNGKey(seed + 1), net)
    streams = _client_streams(np.random.default_rng(seed + 1), n_clients=150)
    with tempfile.TemporaryDirectory() as ckpt:
        on = tnn_engine.TNNEngine(params, net, tnn_engine.TNNServeConfig(
            n_slots=32, learn=True, checkpoint_dir=ckpt, checkpoint_every=8))
        off = tnn_engine.TNNEngine(
            params, net, tnn_engine.TNNServeConfig(n_slots=32))
        reqs_on = [on.submit(s) for s in streams]
        reqs_off = [off.submit(s) for s in streams]
        t0 = time.perf_counter()
        while on.pool.has_work:
            # the learning step computes its outputs at the weights it
            # starts from: serve the same step learning-off at those
            off.params = on.params
            on.step()
            off.step()
        on.checkpoint_wait()
        dt = time.perf_counter() - t0
        st = on.stats()
    if off.pool.has_work:
        raise AssertionError("learning-off twin did not drain in lockstep")
    drift = [f"{st[f'weight_drift_l{i}']:.3f}" for i in range(len(params))]
    bad = [i for i, (a, b) in enumerate(zip(reqs_on, reqs_off))
           if not np.array_equal(a.result(), b.result())]
    _log("learn", f"recurrent layer 2 (rf_total "
         f"{net.layers[1].rf_total}); {len(streams)} streams, "
         f"{int(st['n_steps'])} steps in {dt:.2f} s (incl. compiles); "
         f"engines on {on.engine} off {off.engine}; stdp updates "
         f"{int(st['n_stdp_updates'])}, snapshots {int(st['n_snapshots'])}, "
         f"weight drift {drift}; mismatched streams "
         f"{len(bad)}/{len(streams)}")
    if bad:
        raise AssertionError(f"learning changed outputs of streams {bad[:10]}")
    if st["n_stdp_updates"] <= 0 or st["n_snapshots"] < 1:
        raise AssertionError("no STDP update or no snapshot was taken")
    for eng in (on, off):
        if eng.engine != "pallas":
            raise AssertionError(f"the engine resolved to {eng.engine}")


def phase_lm(seed: int) -> None:
    import numpy as np

    from repro.configs.registry import get_config
    from repro.models import transformer as T
    from repro.serve import Engine, ServeConfig

    cfg = get_config(LM_ARCH)
    max_new, n_slots = 16, 4
    t0 = time.perf_counter()
    params = jax.jit(lambda key: T.init_params(key, cfg))(
        jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(3, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in rng.integers(4, 17, size=6)]
    eng = Engine(params, cfg,
                 ServeConfig(max_len=max(len(p) for p in prompts)
                             + max_new + 2))
    _log("lm", f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
         f"vocab {cfg.vocab_size}, {cfg.param_count() / 1e9:.2f}B params "
         f"({cfg.dtype}); init {init_s:.2f} s; prompt lengths "
         f"{[len(p) for p in prompts]}")
    # the per-request oracle: each prompt served alone, then all of them
    # through fewer slots than requests. Alone means alone in the same
    # slot count: on a TPU a one-row decode matmul rounds differently from
    # a wider one (DESIGN.md section 5.2, experiments/lm_row_rounding.py)
    t0 = time.perf_counter()
    oracle = [eng.serve([p], max_new, n_slots=n_slots)[0] for p in prompts]
    oracle_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    outs = eng.serve(prompts, max_new, n_slots=n_slots)
    serve_s = time.perf_counter() - t0
    bad = [i for i, (a, b) in enumerate(zip(outs, oracle))
           if not np.array_equal(a, b)]
    _log("lm", f"oracle {oracle_s:.2f} s (incl. compile), {n_slots}-slot "
         f"serve {serve_s:.2f} s (incl. compile), {eng.n_steps} steps; "
         f"tokens {[len(o) for o in outs]}; mismatched requests "
         f"{len(bad)}/{len(prompts)}")
    for i in bad:
        diff = int(np.argmax(outs[i][:len(oracle[i])] != oracle[i]))
        _log("lm", f"request {i} diverges at token {diff}: batched "
             f"{outs[i].tolist()} vs oracle {oracle[i].tolist()}")
    if bad:
        raise AssertionError(f"requests {bad} differ from the oracle")


def phase_four_chips(seed: int) -> None:
    import numpy as np

    from repro.core import network
    from repro.serve import tnn_engine
    from repro.sharding import specs

    net = _tnn_network()
    params = network.init_network(jax.random.PRNGKey(seed), net)
    streams = _client_streams(np.random.default_rng(seed), n_clients=300)
    scfg = tnn_engine.TNNServeConfig(n_slots=128, backend="pallas")
    mesh = specs.tnn_mesh(n_column=4)
    sharded = tnn_engine.TNNEngine(params, net, scfg, mesh=mesh)
    spans = [len(p.sharding.device_set) for p in sharded.params]
    _log("4chip", f"mesh {dict(mesh.shape)}; weight shardings "
         f"{[str(p.sharding.spec) for p in sharded.params]} over {spans} "
         f"devices")
    if spans != [4] * len(spans):
        raise AssertionError(f"weights not spread over 4 devices: {spans}")
    single = tnn_engine.TNNEngine(params, net, scfg)
    reqs_sh, first_sh, drain_sh = _serve(sharded, streams)
    reqs_1, first_1, drain_1 = _serve(single, streams)
    bad = [i for i, (a, b) in enumerate(zip(reqs_sh, reqs_1))
           if not np.array_equal(a.result(), b.result())]
    st = sharded.stats()
    _log("4chip", f"{len(streams)} streams; sharded steps "
         f"{_engine_steps(st)}, first step (incl. compile) {first_sh:.2f} s, "
         f"rest {drain_sh:.2f} s; single-device first step {first_1:.2f} s, "
         f"rest {drain_1:.2f} s; output spike share {_spread(reqs_1)}; "
         f"mismatched streams {len(bad)}/{len(streams)}")
    if bad:
        raise AssertionError(f"streams {bad[:10]} differ across 1 vs 4 chips")
    if _engine_steps(st) != {"pallas": int(st["n_steps"])}:
        raise AssertionError(f"not every sharded step ran pallas: {st}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded TNN phase")
    args = ap.parse_args()

    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s); JAX found "
              f"{len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 2

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.kernels import common
    from repro.launch import compile_cache

    cache = compile_cache.enable()
    if common.use_interpret():
        raise AssertionError("Pallas kernels would run in the interpreter")
    print(f"jax {jax.__version__}; {len(devices)} x "
          f"{devices[0].device_kind}; compile cache {cache}; seed "
          f"{args.seed}", flush=True)

    phases = ([("4chip", phase_four_chips)] if args.four_chips else
              [("tnn", phase_tnn), ("learn", phase_learn), ("lm", phase_lm)])
    for name, fn in phases:
        t0 = time.perf_counter()
        fn(args.seed)
        _log(name, f"ok in {time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
