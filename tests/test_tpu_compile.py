"""The RNL kernels and layer 1's jitted forward compile for a TPU v5e.

Ahead-of-time compiles against a *described* v5e:2x2 topology (no chip
needed, the TPU compiler ships with libtpu): Mosaic refuses here what
the Pallas interpreter accepts on CPU — e.g. an output block whose last
dim is neither lane-aligned nor the whole array. Shapes are the
deployment widths ``chip_smoke.py`` serves: Q=16 neurons per column,
n=64 (layer 1) and n=256 (layer 2) dendrites, compacted widths on the
lane-aligned bucket ladder; and the layer kernel at the chip benchmark's
``tnn-proto-2l`` layers.

The topology is described inside a fixture, never at import: only one
process may load libtpu at a time, and every test worker imports this
file. Where it cannot be described (no libtpu), every test skips.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import layer, network
from repro.kernels import rnl_neuron

Q, B, T = 16, 128, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except RuntimeError as e:
        # only a missing libtpu skips; any other failure (e.g. libtpu held
        # by another process) fails, so the Mosaic guard is never lost
        if "TPU support not installed" not in str(e):
            raise
        pytest.skip(f"libtpu is not installed: {e}")


@pytest.fixture
def one_chip(topo, monkeypatch):
    """Sharding on one described chip, with Mosaic lowering forced.

    ``use_interpret()`` is read when a kernel is traced, and jit caches
    traces by shape alone: clear them on both sides so no interpret-mode
    trace from another test is reused here, and no Mosaic trace leaks
    into a later CPU test. The persistent cache stays off — a compile for
    a described chip cannot be read back without one.
    """
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(sharding, *shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("q,n", [(Q, 64), (Q, 256), (200, 64)])
def test_bank_kernel_compiles(one_chip, q, n):
    # q=200: a bank wider than one 128-lane tile pads to 256 and tiles at 128
    _assert_mosaic(rnl_neuron.rnl_fire_times.lower(
        _spec(one_chip, B, n), _spec(one_chip, q, n),
        t_steps=T, threshold=20, k=2))


@pytest.mark.parametrize("n_columns,n,q,bsz,with_clip", [
    (256, 64, Q, B, False), (16, 256, Q, B, False),
    (16, 256 + Q, Q, B, False), (256, 64, Q, B, True),
    # the benchmark's tnn-proto-2l layers at its 128 and 32 slots
    (625, 32, 12, 128, False), (625, 12, 10, 128, False),
    (625, 32, 12, 32, True), (625, 12, 10, 32, False)])
def test_layer_kernel_compiles(one_chip, n_columns, n, q, bsz, with_clip):
    _assert_mosaic(rnl_neuron.rnl_fire_times_layer.lower(
        _spec(one_chip, n_columns, bsz, n), _spec(one_chip, n_columns, q, n),
        t_steps=T, threshold=20, k=2, with_clip=with_clip))


@pytest.mark.parametrize("s", [8, 16, 32, 64, 128, 256])
def test_compact_kernel_compiles(one_chip, s):
    # 16 columns folded into the batch, as fire_times_bank does
    rows = 16 * B
    _assert_mosaic(rnl_neuron.rnl_fire_times_compact.lower(
        _spec(one_chip, rows, s), _spec(one_chip, rows, Q, s),
        t_steps=T, threshold=20, k=2))


def test_layer1_forward_compiles(one_chip):
    l1 = layer.TNNLayer(n_columns=256, rf_size=64, n_neurons=Q,
                        threshold=20, t_steps=T, dendrite="catwalk", k=2,
                        backend="pallas")
    net = network.make_network([l1])
    params = (_spec(one_chip, 256, Q, 64, dtype=jnp.float32),)
    fwd = jax.jit(lambda p, v: network.forward(p, v, net).out)
    _assert_mosaic(fwd.lower(params, _spec(one_chip, B, net.n_inputs)))


def test_bank_kernel_keeps_its_trace_name(one_chip):
    """The device trace names the layer kernel's custom call after the
    ``pallas_call``'s ``name=`` (``rnl_fire_times_layer.N``, which the chip
    benchmark's ``rnl_kernel_ms`` matches); the layer's op scopes only add
    to its op name."""
    l1 = layer.TNNLayer(n_columns=16, rf_size=64, n_neurons=Q,
                        threshold=20, t_steps=T, dendrite="catwalk", k=2,
                        backend="pallas")
    net = network.make_network([l1])
    params = (_spec(one_chip, 16, Q, 64, dtype=jnp.float32),)
    fwd = jax.jit(lambda p, v: network.forward(p, v, net).out)
    text = fwd.lower(params, _spec(one_chip, B, net.n_inputs)).compile() \
        .as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert calls
    for line in calls:
        assert re.match(r"\s*(ROOT )?%rnl_fire_times_layer(\.\d+)? = ", line)
        assert "/bank/" in line
