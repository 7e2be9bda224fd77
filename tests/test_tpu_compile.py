"""Ask the TPU's compiler, without a TPU: every compiled program of the live
``--accelerator`` path is lowered and compiled for a DESCRIBED v5e:2x2
topology at the real 16-validator window buckets (hashgraph/accel.py
``prewarm_buckets``), the largest window the defaults allow
(SuspendLimit 100 x 16 validators -> E 2048) and a headroom bucket.

A compile that passes is not a chip run — nothing executes, so this says
nothing about results or times (``python chip_smoke.py`` on the chip does).
It guards what interpret mode and host XLA cannot see: Mosaic lowering of
the Pallas kernels, buffer donation actually aliasing, the collectives of
the witness-sharded programs, and device-memory fit.

All of it lives in THIS one file: only the xdist worker that is handed the
file loads the TPU library (the topology is described inside a fixture,
never at import), and the compiles run in the test's own process.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from babble_tpu.ops import voting

HBM_BYTES = 16 * 1024**3  # one v5e chip

# the buckets prewarm_buckets compiles for >= 12 validators (P=16, S=1)
BUCKET_LIVE = (128, 1024, 16, 1, 32)
BUCKET_LIVE_W256 = (256, 1024, 16, 1, 32)
# SuspendLimit 100 x 16 validators bounds the undetermined window near E 2048
BUCKET_SUSPEND_LIMIT = (256, 2048, 16, 1, 32)
# headroom: 64 validators, two peer-set slots
BUCKET_HEADROOM = (1024, 8192, 64, 2, 64)


@pytest.fixture(scope="module")
def topo():
    """The described (not attached) v5e:2x2 topology, with the persistent
    compile cache off around these tests: conftest turns it on, and a
    compile for a described chip is written to it but can never be read
    back without the chip."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("dp", "sp"))


def _struct(a, sharding):
    import jax

    a = np.asarray(a)
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _sweep_structs(key, sharding, batch=None):
    win = voting.dummy_window(*key)
    out = []
    for f in voting._WIN_FIELDS:
        a = np.asarray(getattr(win, f))
        if batch is not None:
            a = np.broadcast_to(a, (batch,) + a.shape)
        out.append(_struct(a, sharding))
    return out


def _resident_structs(key, resident_shardings, other):
    from babble_tpu.ops import window_state as ws

    win = voting.dummy_window(*key)
    bufs = [
        _struct(getattr(win, f), s)
        for f, s in zip(ws.RESIDENT_FIELDS, resident_shardings)
    ]
    delta = [_struct(a, other) for a in ws._empty_delta(key)]
    fresh = [_struct(getattr(win, f), other) for f in ws.FRESH_FIELDS]
    return bufs + delta + fresh


def _fits(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total} bytes do not fit one v5e chip"
    return m


# -- Pallas kernels: Mosaic must accept them at real widths -------------------


@pytest.mark.parametrize("W,P,S", [(128, 16, 1), (256, 16, 1), (1024, 64, 2)])
def test_member_ss_counts_pallas_compiles(one_chip, W, P, S):
    from babble_tpu.ops.pallas_kernels import member_ss_counts_pallas

    la = _struct(np.zeros((W, P), np.int32), one_chip)
    member = _struct(np.zeros((S, P), bool), one_chip)
    compiled = member_ss_counts_pallas.lower(la, la, member).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("E,P", [(1024, 16), (4096, 40)])
def test_strongly_see_pallas_compiles(one_chip, E, P):
    from babble_tpu.ops.pallas_kernels import strongly_see_pallas

    la = _struct(np.zeros((E, P), np.int32), one_chip)
    compiled = strongly_see_pallas.lower(
        la, la, super_majority=2 * P // 3 + 1
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_fused_sweep_with_pallas_kernel_compiles(one_chip, monkeypatch):
    """The whole sweep with the Pallas strongly-see traced in — what
    BABBLE_PALLAS=1 runs on the chip (pallas_mode() is steered here, in
    the test: this process's default backend is the cpu one)."""
    import jax

    monkeypatch.setattr(voting, "pallas_mode", lambda: "tpu")
    compiled = jax.jit(voting._sweep_core).lower(
        *_sweep_structs(BUCKET_LIVE, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


# -- single-device programs ---------------------------------------------------


@pytest.mark.parametrize(
    "key",
    [BUCKET_LIVE, BUCKET_LIVE_W256, BUCKET_SUSPEND_LIMIT, BUCKET_HEADROOM],
    ids=["live", "live-w256", "suspend-limit", "headroom"],
)
def test_sweep_jit_compiles(one_chip, key):
    compiled = voting._sweep_jit.lower(*_sweep_structs(key, one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()  # the XLA einsum path
    _fits(compiled)


def test_batched_sweep_jit_compiles_at_max_batch(one_chip):
    from babble_tpu.hashgraph.sweep_batcher import SweepBatcher

    B = SweepBatcher.MAX_BATCH
    assert B == 16
    compiled = voting._batched_sweep_jit.lower(
        *_sweep_structs(BUCKET_LIVE, one_chip, batch=B)
    ).compile()
    _fits(compiled)


@pytest.mark.parametrize(
    "key", [BUCKET_LIVE, BUCKET_SUSPEND_LIMIT], ids=["live", "suspend-limit"]
)
def test_resident_jit_compiles_and_donation_aliases(one_chip, key):
    """CPU XLA ignores donate_argnums; the chip's compiler must turn the 11
    donated window buffers into input/output aliases."""
    from babble_tpu.ops import window_state as ws

    compiled = ws._resident_jit.lower(
        *_resident_structs(key, [one_chip] * 11, one_chip)
    ).compile()
    text = compiled.as_text()
    assert "input_output_alias" in text
    m = _fits(compiled)
    assert m.alias_size_in_bytes > 0


# -- witness-sharded programs on a 4-device mesh ------------------------------


def _mesh_sweep_shardings(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from babble_tpu.parallel.voting_shard import AXES

    w1 = NamedSharding(mesh, P(AXES))
    w2 = NamedSharding(mesh, P(AXES, None))
    rep = NamedSharding(mesh, P(None))
    # _WIN_FIELDS order: la_w/fd_w [W, P]; rounds_w..mid_w [W]; rest replicated
    return [rep, rep, w2, w2, w1, w1, w1, w1] + [rep] * 10


def test_sharded_sweep_compiles_with_collectives(mesh4):
    import jax

    from babble_tpu.parallel import voting_shard

    shardings = _mesh_sweep_shardings(mesh4)
    win = voting.dummy_window(*BUCKET_LIVE)
    args = [
        _struct(getattr(win, f), s)
        for f, s in zip(voting._WIN_FIELDS, shardings)
    ]
    # built as voting_shard._jitted builds it, but not through its cache:
    # that is keyed on device ids, which the described chips share with
    # conftest's virtual cpu devices
    compiled = jax.jit(voting_shard.sharded_sweep_fn(mesh4)).lower(
        *args
    ).compile()
    text = compiled.as_text()
    assert "all-gather" in text and "all-reduce" in text
    assert len(compiled.output_shardings.device_set) == 4
    _fits(compiled)


def test_sharded_resident_compiles_with_collectives_and_aliases(mesh4):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from babble_tpu.parallel import voting_shard

    rep = NamedSharding(mesh4, P(None))
    res_sh = voting_shard.resident_shardings(mesh4)
    # as voting_shard.resident_jitted builds it (see the cache note above)
    fn = jax.jit(
        voting_shard.resident_sweep_fn(mesh4),
        donate_argnums=tuple(range(11)),
        out_shardings=(res_sh, rep),
    )
    compiled = fn.lower(*_resident_structs(BUCKET_LIVE, res_sh, rep)).compile()
    text = compiled.as_text()
    assert "all-gather" in text and "all-reduce" in text
    assert "input_output_alias" in text
    new_bufs_sh, out_sh = compiled.output_shardings
    assert len(out_sh.device_set) == 4
    assert all(len(s.device_set) == 4 for s in new_bufs_sh)
    _fits(compiled)
