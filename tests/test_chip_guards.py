"""Guards that keep a chip run from degrading in silence: a failing
replay kernel propagates instead of posing as a chain cut, the kernels
interpret only on the CPU, a snapshot never skips a leaf, peak rates
come from a table keyed by chip, the compile cache lands where it is
told, and the trainer's depth cut reaches the model."""
import argparse
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression.packed import PackedDiff
from repro.core import recovery as rec
from repro.core.snapshot import ShardedPendingSnapshot, start_host_transfer
from repro.kernels import ops
from repro.launch import compile_cache
from repro.launch.mesh import PEAKS, peaks
from repro.optim.adam import AdamState

HYPER = dict(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8)


def _setup(seed, shapes, kind="topk", n=4):
    """Params/opt for ``shapes`` plus an n-long chain of compressed
    differentials (shapes unique to each test, so the jitted replay
    traces afresh and sees any monkeypatch)."""
    rng = np.random.default_rng(seed)
    params = {k: jnp.asarray(rng.standard_normal(s), jnp.float32)
              for k, s in shapes.items()}
    zeros = {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
    opt = AdamState(zeros, dict(zeros), jnp.zeros((), jnp.int32))
    comp = ops.topk_compress if kind == "topk" else ops.packed_compress
    diffs = [(i + 1, {k: comp(jnp.asarray(rng.standard_normal(s),
                                          jnp.float32), 0.05, block=256)
                      for k, s in shapes.items()}) for i in range(n)]
    return params, opt, diffs


def test_replay_device_kernel_failure_propagates(monkeypatch):
    params, opt, diffs = _setup(1, {"a": (37, 41), "b": (13,)})

    def broken(*a, **k):
        raise RuntimeError("kernel failed to compile")
    monkeypatch.setattr(ops, "fused_decode_apply", broken)
    with pytest.raises(RuntimeError, match="kernel failed"):
        rec.replay_device(params, opt, diffs, **HYPER)


def test_replay_parallel_decode_failure_propagates(monkeypatch):
    params, opt, diffs = _setup(2, {"a": (29, 43)}, kind="packed")

    def broken(*a, **k):
        raise RuntimeError("kernel failed to run")
    monkeypatch.setattr(ops, "packed_decompress", broken)
    with pytest.raises(RuntimeError, match="kernel failed"):
        rec.replay_parallel(params, opt, diffs, **HYPER)


@pytest.mark.parametrize("replay", [rec.replay_device, rec.replay_parallel])
def test_corrupt_bytes_still_cut_the_chain(replay):
    """A torn container and a differential that does not match the
    model are corruption: both paths cut at that differential."""
    params, opt, diffs = _setup(3, {"a": (31, 47)}, kind="packed", n=5)
    pd = diffs[2][1]["a"]
    diffs[2] = (3, {"a": PackedDiff(pd.q[:-1], pd.indices[:-1],
                                    pd.scale[:-1], pd.shape, pd.block)})
    _, o, n = replay(params, opt, diffs, window=2, **HYPER)
    assert n == 2 and int(o.count) == 2
    diffs[2] = (3, {"a": diffs[1][1]["a"], "extra": diffs[1][1]["a"]})
    _, o, n = replay(params, opt, diffs, window=2, **HYPER)
    assert n == 2 and int(o.count) == 2


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_kernels_interpret_only_on_cpu(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interpret()
    else:
        assert ops._interpret() is want


@pytest.mark.parametrize("sharded", [False, True])
def test_snapshot_refuses_a_leaf_it_cannot_address(sharded):
    leaf = mock.Mock(spec=jax.Array)
    leaf.is_fully_addressable = False
    leaf.shape, leaf.nbytes = (8,), 32
    tree = {"ok": jnp.ones(3), "remote": leaf}
    with pytest.raises(ValueError, match="not fully addressable"):
        (ShardedPendingSnapshot(tree, shards=2) if sharded
         else start_host_transfer(tree))
    leaf.copy_to_host_async.assert_not_called()


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="no published peak rates"):
        peaks("TPU v9 imaginary")
    assert set(PEAKS) == {"TPU v5 lite"}


def test_replay_roofline_picks_bandwidth_by_platform(monkeypatch):
    from repro.analysis import roofline
    monkeypatch.setattr(roofline, "measured_copy_bandwidth", lambda: 1e9)
    cpu = roofline.replay_roofline(100, 10, 2, jax.devices()[0])
    assert cpu["bandwidth"] == 1e9
    chip = mock.Mock(platform="tpu", device_kind="TPU v5 lite")
    assert roofline.replay_roofline(100, 10, 2, chip)["bandwidth"] == 819e9
    with pytest.raises(KeyError):
        roofline.replay_roofline(
            100, 10, 2, mock.Mock(platform="tpu", device_kind="TPU v0"))


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    cc.reset_cache()


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch,
                                                    restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.enable_compile_cache() == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_layers_cuts_depth_and_run_reports_recovery(tmp_path):
    from repro.launch import train as T
    args = T.build_parser().parse_args(
        ["--arch", "gpt2-l", "--reduced", "--layers", "1", "--steps", "4",
         "--batch", "2", "--seq", "16", "--full-interval", "2",
         "--batch-size", "1", "--fail-at", "3", "--log-every", "0",
         "--ckpt-dir", str(tmp_path / "ck")])
    assert args.layers == 1
    res = T.run(args)
    assert len(res.losses) == 4 and np.isfinite(res.losses).all()
    assert res.recoveries == [{"fail_at": 3, "applied": 1, "step": 3}]
    # a hand-built namespace without the flag keeps the published depth
    T.run(argparse.Namespace(arch="gpt2-l", reduced=True, steps=1, batch=1,
                             seq=8, lr=1e-3, rho=0.05, strategy="none",
                             seed=0, log_every=0, fail_at=0))
