"""Every kernel the execution backend dispatches compiles for a TPU v5e.

Compiled here for a chip that is described, not attached (the TPU compiler
ships with JAX): interpret mode cannot see what Mosaic refuses — blocks whose
last two dims are not (8, 128)-aligned, DMA slices narrower than 128 lanes,
scratch beyond VMEM.  Covered: ``rsnn_infer``, ``rsnn_step_sessions``, fused
``rsnn_train`` and ``rsnn_forward`` in ``blocked`` and ``dma`` streaming,
float and quantized, at the Braille (12-38-3), cue (40-100-2) and full-core
(256-256-16) widths; the fused train tile's VMEM guard against the
compiler's own limit; the split ``eprop_update``; and the data-parallel
backend's quantized END_B commit and session step over a described 2x2 mesh.

The topology is described inside module-scoped fixtures, never at import:
only one process may hold the TPU library, so a test worker that imported
this file must not grab it unless it runs these tests.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.backend import ExecutionBackend, RuntimeConfig
from repro.core.quant import DW_COMMIT_SPEC
from repro.core.rsnn import Presets, init_params, trainable
from repro.kernels import eprop_update as E
from repro.kernels import ops
from repro.kernels import rsnn_step as R

QUANT = Presets.braille(quantized=True).neuron.quant

# (name, n_in, n_hid, n_out, ticks, batch): batches that are not whole
# sublane groups, and one that spans several tiles
WIDTHS = [
    ("braille", 12, 38, 3, 256, 50),
    ("cue", 40, 100, 2, 150, 5),
    ("core", 256, 256, 16, 150, 200),
]
OPS = ("infer", "sessions", "train", "forward")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_call(op, N, H, O, T, B, stream, quant, sharding):
    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    kw = dict(alpha=0.99, kappa=0.2, quant=quant, stream=stream)
    w = [S(N, H), S(H, H), S(H, O)]
    if op == "infer":
        return (lambda r, v, *w: R.rsnn_infer(r, v, *w, **kw),
                [S(T, B, N), S(T, B), *w])
    if op == "sessions":
        return (lambda r, l, v, a, b, c, d, e, *w: R.rsnn_step_sessions(
                    r, l, v, a, b, c, d, e, *w, **kw),
                [S(T, B, N), S(T, B), S(T, B), S(B, H), S(B, H), S(B, O),
                 S(B, O), S(B, 1), *w])
    if op == "train":
        return (lambda r, ys, v, *w: E.rsnn_train(r, ys, v, *w, **kw),
                [S(T, B, N), S(B, O), S(T, B), *w, S(H, O)])
    return (lambda r, *w: R.rsnn_forward(r, *w, **kw), [S(T, B, N), *w])


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "quant"])
@pytest.mark.parametrize("stream", ["blocked", "dma"])
@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("width", WIDTHS, ids=[w[0] for w in WIDTHS])
def test_kernel_compiles_for_v5e(one_chip, width, op, stream, quantized):
    _, N, H, O, T, B = width
    fn, args = _kernel_call(op, N, H, O, T, B, stream,
                            QUANT if quantized else None, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("stream", ["blocked", "dma"])
@pytest.mark.parametrize("width", [WIDTHS[0], WIDTHS[2]],
                         ids=[WIDTHS[0][0], WIDTHS[2][0]])
def test_fused_train_vmem_guard_is_the_compile_limit(one_chip, width, stream):
    """The fused train tile's VMEM guard sits where the compiler's limit
    is: the longest one-group (Bt=8) tile it admits compiles, well past
    Mosaic's default 16 MiB scoped slice, and one tick more is refused at
    trace time with the actionable ValueError."""
    _, N, H, O, _, _ = width
    fn, _ = _kernel_call("train", N, H, O, 1, 8, stream, None, one_chip)

    def args(T):
        return _kernel_call("train", N, H, O, T, 8, stream, None, one_chip)[1]

    def admitted(T):
        try:
            jax.eval_shape(fn, *args(T))
            return True
        except ValueError:
            return False

    lo, hi = 1, 2**13   # admitted(lo), not admitted(hi)
    assert admitted(lo) and not admitted(hi)
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if admitted(mid) else (lo, mid)
    assert R.fused_train_bytes(lo, 8, N, H, O) > 16 * 2**20
    compiled = jax.jit(fn).lower(*args(lo)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    with pytest.raises(ValueError, match="beyond the core"):
        jax.eval_shape(fn, *args(hi))


@pytest.mark.parametrize("width", WIDTHS, ids=[w[0] for w in WIDTHS])
def test_split_eprop_update_compiles_for_v5e(one_chip, width):
    _, N, H, O, T, B = width

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    fn = lambda h, x, p, z, e, fb: E.eprop_update(h, x, p, z, e, fb,
                                                   kappa=0.2)
    jax.jit(fn).lower(S(T, B, H), S(T, B, N), S(T, B, H), S(T, B, H),
                      S(T, B, O), S(H, O)).compile()


def test_data_parallel_backend_compiles_for_2x2(topo, monkeypatch):
    """The quantized commit-grid END_B train step and the session step of a
    backend over a described 2x2 data mesh: the Pallas kernel inside
    ``shard_map`` on every chip, per-device memory within HBM."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("data",))
    cfg = Presets.braille(n_classes=3, num_ticks=256, quantized=True)
    be = ExecutionBackend(cfg, runtime=RuntimeConfig(
        backend="kernel", mesh=mesh, event_density=0.08,
        commit_grid=DW_COMMIT_SPEC))
    assert be.num_devices == 4 and be._stream == "dma"
    rep = NamedSharding(mesh, PartitionSpec())

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rep)

    weights = jax.tree.map(lambda a: S(*a.shape),
                           trainable(init_params(jax.random.key(0), cfg)))
    T, B, N, H, O = 256, 50, cfg.n_in, cfg.n_hid, cfg.n_out
    state = {"v": S(B, H), "z": S(B, H), "y": S(B, O), "acc_y": S(B, O),
             "n_spk": S(B, 1)}
    for compiled in (
        be._jit_train.lower(weights, S(T, B, N), S(B, O), S(T, B)).compile(),
        be._jit_step_sessions.lower(
            weights, S(T, B, N), S(T, B), S(T, B), state).compile(),
    ):
        assert "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                      + mem.temp_size_in_bytes)
        assert 0 < per_device < 16 * 2**30
