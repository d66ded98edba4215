"""Slot-based TNN serving demo: many synthetic clients, one volley engine.

Simulates N concurrent clients, each streaming a short burst of GRF-encoded
feature vectors (Gaussian receptive field population coding — the sparse,
bursty volley shape the Catwalk dendrite is built for), served through the
slot-based TNN engine: requests flow through a fixed pool of B slots with
continuous re-fill, every gamma cycle one batched ``network.forward`` over
the live slots (backend-dispatched ``fire_times_bank``).

Verifies the engine's spike-time outputs are bit-exact against unbatched
per-request ``TNNNetwork`` inference, then prints per-request measured
spike density, the neuron-bank engine the backend resolved to at
construction (DESIGN.md §3.7), and throughput/latency stats.

Run:  PYTHONPATH=src python examples/serve_tnn.py [--clients 64 --slots 8]
"""

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import coding, layer, network
from repro.launch import compile_cache
from repro.serve import tnn_engine


def build_network(t_steps: int = 16):
    """Two-layer TNN over 4 features x 8 GRF lines = 32 input lines."""
    l1 = layer.TNNLayer(n_columns=4, rf_size=8, n_neurons=4, threshold=8,
                        t_steps=t_steps, dendrite="catwalk", k=2)
    l2 = layer.TNNLayer(n_columns=2, rf_size=8, n_neurons=4, threshold=6,
                        t_steps=t_steps, dendrite="catwalk", k=2)
    return network.make_network([l1, l2])


def synth_clients(n_clients: int, n_features: int, n_fields: int,
                  t_max: int, seed: int = 0):
    """Each client: a random-length burst of GRF-encoded feature vectors."""
    rng = np.random.default_rng(seed)
    streams = []
    for _ in range(n_clients):
        n_cycles = int(rng.integers(1, 7))
        feats = rng.random((n_cycles, n_features)).astype(np.float32)
        enc = coding.grf_encode(jnp.asarray(feats), n_fields, t_max)
        streams.append(np.asarray(enc).reshape(n_cycles, -1))
    return streams


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "scan", "closed_form", "event",
                             "pallas"])
    args = ap.parse_args()
    compile_cache.enable()

    net = build_network()
    params = network.init_network(jax.random.PRNGKey(0), net)
    streams = synth_clients(args.clients, n_features=4, n_fields=8,
                            t_max=net.layers[0].t_steps)
    total_volleys = sum(s.shape[0] for s in streams)
    print(f"serving {args.clients} clients ({total_volleys} volleys, "
          f"{net.n_inputs} lines) through {args.slots} slots, "
          f"backend={args.backend}")

    eng = tnn_engine.TNNEngine(
        params, net,
        tnn_engine.TNNServeConfig(n_slots=args.slots, backend=args.backend))
    reqs = [eng.submit(s) for s in streams]
    eng.run()
    results = [r.result() for r in reqs]

    mismatches = 0
    per_layer = None
    for i, (stream, result) in enumerate(zip(streams, results)):
        if i == 0:
            # one pass serves double duty: stream 0's reference outputs
            # AND the per-layer density diagnostic printed below come from
            # the same stack run (engine outputs are bit-exact vs batched
            # and unbatched network.forward alike)
            res = network.forward(params, jnp.asarray(stream), net,
                                  with_densities=True)
            ref, per_layer = np.asarray(res.out), res.densities
        else:
            ref = tnn_engine.reference_outputs(params, net, stream)
        if not np.array_equal(ref, result):
            mismatches += 1
    st = eng.stats()
    # measured per-request density; every cycle ran eng.engine
    for req in reqs[:8]:
        print(f"  req {req.req_id:3d}: {req.n_cycles} cycles, "
              f"density {req.density:.2f}")
    if len(reqs) > 8:
        print(f"  ... ({len(reqs) - 8} more requests)")
    dens = " -> ".join(f"{d:.2f}" for d in per_layer)
    print(f"layer input densities (req 0): {dens}")
    print(f"steps={int(st['n_steps'])}  "
          f"occupancy={st['slot_occupancy']:.2f}  "
          f"engine={eng.engine}  "
          f"throughput={st.get('volleys_per_s', 0.0):.0f} volleys/s")
    print(f"latency ms: mean={st['latency_ms_mean']:.1f} "
          f"p50={st['latency_ms_p50']:.1f} p95={st['latency_ms_p95']:.1f} "
          f"(queue wait {st['wait_ms_mean']:.1f}, "
          f"service {st['service_ms_mean']:.1f})")
    if mismatches:
        print(f"FAIL: {mismatches}/{len(streams)} requests diverge from "
              f"unbatched TNNNetwork inference")
        return 1
    print(f"OK: all {len(streams)} requests bit-exact vs unbatched "
          f"TNNNetwork inference")
    return 0


if __name__ == "__main__":
    sys.exit(main())
