"""Recovery: differential replay (Algorithm 1) — serial and parallel.

Serial replay applies each differential through Adam in sequence:
``M_{j+1} = M_j + Adam(G_j)`` — n optimizer merges for n differentials.

Parallel recovery (paper §VII, Fig. 10) merges in log(n) depth. The
paper's pairwise merge is exact only for *state-delta* differentials
(Naïve DC); LowDiff differentials are gradients that pass through a
*stateful* optimizer. TPU/JAX adaptation: Adam's moment recurrences are
affine, so we parallelize them *exactly* with an associative scan
(log-depth, MXU-free elementwise work) — all intermediate (mu_j, nu_j)
drop out of one ``lax.associative_scan``, every step's param delta is then
computed in parallel, and a single sum produces M_n. This is the paper's
log(n) recovery without its approximation.

Device-resident replay (``replay_device``) goes one step further: the
compressed payloads themselves are staged to the device — a fraction of
the dense bytes over the interconnect — and a single jitted
``lax.scan`` decodes and applies each differential with the fused
decompress-and-apply kernels (``kernels.replay``); no dense gradient
stack ever exists on host or in HBM, and window N+1's payloads upload
while window N scans (double-buffered H2D staging).
"""
from __future__ import annotations

import functools
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compression.sparse import SparseGrad
from repro.optim.adam import AdamState


def load_latest_chain(store):
    """Load the newest full checkpoint and the ordered differentials
    after it from whatever storage backend the store wraps (the backend
    re-assembles sharded leaves, hits the memory tier, or fetches and
    checksum-verifies remote chunks transparently).

    A full checkpoint that cannot be read back — missing blob, a
    corrupt frame (leaf sha256 mismatch), or a remote tier whose
    bounded re-fetches never produced checksum-clean chunks — does not
    abort recovery: the loader falls back to the next older full and
    replays the longer differential chain from there. Entries the
    maintenance scrubber quarantined were already removed from the
    manifest's chain kinds, so they are skipped proactively without
    touching storage at all.

    The fallback order is *source-aware* (``order_fulls``): fulls are
    preferred by the state they actually represent (``state_step``),
    then by nominal step, then by the durability of the tier that
    recorded them (durable > memory > peer). On a replacement host the
    peer-adopted entries are typically the ONLY entries — peer-first
    recovery at network speed — while on a host whose durable storage
    survived, a stale peer-served replica can never shadow a newer
    durable full. Returns (state, [(step, payload), ...]); raises
    FileNotFoundError when no full checkpoint is loadable."""
    from repro.checkpoint.io import FrameCorruptionError
    from repro.checkpoint.remote import RetryExhaustedError
    from repro.checkpoint.store import order_fulls
    fulls = order_fulls(store.manifest["fulls"])
    if not fulls:
        raise FileNotFoundError("no full checkpoint")
    last_err = None
    for entry in fulls:
        try:
            state = store.load_full(entry)
        except (FileNotFoundError, RetryExhaustedError,
                FrameCorruptionError) as e:
            last_err = e
            continue
        return state, store.diffs_after(entry["step"])
    raise FileNotFoundError(
        f"none of {len(fulls)} full checkpoints is loadable "
        f"(last error: {last_err})")


def contiguous_prefix(start: int, diffs: List[Tuple[int, Any]],
                      stride: int = 1) -> List[Tuple[int, Any]]:
    """Longest prefix of ``diffs`` whose steps advance by ``stride``
    from ``start``. Replaying *past* a hole — a differential whose
    async write-back never landed before the crash, leaving a
    mid-chain gap that ``_prune_missing`` (which assumes missing blobs
    are a FIFO suffix) cannot repair — would silently corrupt the
    recovered state, so callers that know their differential cadence
    cut the chain at the first gap and recover to the last provably
    consistent step instead. LowDiff emits one differential per
    iteration, hence stride 1; strategies with a sparser cadence pass
    their own stride."""
    out = []
    expect = start + stride
    for s, p in diffs:
        if s != expect:
            break
        out.append((s, p))
        expect = s + stride
    return out


def upload(tree, span: str, *, wait: bool):
    """Put a recovered host tree on the device at the point recovery
    chooses, not implicitly at whichever dispatch first reads it.
    Counted in ``COPY_METER``'s H2D counters and traced as ``span``
    with its ``bytes``. ``wait``: the span lasts until the bytes have
    landed; otherwise it bounds only the issue of the transfers."""
    from repro.checkpoint.io import COPY_METER
    from repro.obs.trace import trace_span
    with trace_span(span, "recovery") as sp:
        nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
        out = jax.device_put(tree)
        if wait:
            jax.block_until_ready(out)
        COPY_METER.add_h2d(nbytes)
        sp.set(bytes=nbytes)
    return out


def _is_compressed(x):
    from repro.compression.packed import PackedDiff
    from repro.compression.quant import QuantGrad
    return isinstance(x, (SparseGrad, QuantGrad, PackedDiff))


def maybe_decompress(payload):
    leaves = jax.tree.leaves(payload, is_leaf=_is_compressed)
    if any(_is_compressed(l) for l in leaves):
        return jax.tree.map(lambda l: l.dense() if _is_compressed(l) else l,
                            payload, is_leaf=_is_compressed)
    return payload


def _use_pallas() -> bool:
    # Pallas kernels compile natively on TPU; on CPU (interpret mode is
    # trace-speed) the jnp oracles inside the same jitted program are
    # the fast path and compute identical bits.
    return jax.default_backend() == "tpu"


def _fused_step(params, mu, nu, hyper, payload, use_pallas: bool):
    """Apply one differential — still in wire form — to every leaf via
    the fused decompress-and-apply kernels. Shared by serial replay and
    the device-resident scan so the two are bit-identical."""
    from repro.kernels import ops
    p_leaves, treedef = jax.tree.flatten(params)
    g_leaves = jax.tree.leaves(payload, is_leaf=_is_compressed)
    if len(g_leaves) != len(p_leaves):
        raise ValueError(
            f"differential has {len(g_leaves)} leaves, model has "
            f"{len(p_leaves)}")
    out = [ops.fused_decode_apply(g, p, m, v, hyper, use_pallas=use_pallas)
           for g, p, m, v in zip(g_leaves, p_leaves,
                                 jax.tree.leaves(mu), jax.tree.leaves(nu))]
    return (jax.tree.unflatten(treedef, [o[0] for o in out]),
            jax.tree.unflatten(treedef, [o[1] for o in out]),
            jax.tree.unflatten(treedef, [o[2] for o in out]))


def replay_serial(params, opt: AdamState, diffs: List[Tuple[int, Any]], *,
                  lr=1e-3, b1=0.9, b2=0.999, eps=1e-8):
    """Apply each differential in order. diffs: [(step, payload)].

    Each step runs the fused decompress-and-apply path: the compressed
    payload is decoded *inside* the jitted Adam update (no dense host
    intermediate), which makes serial replay the bit-exact reference
    for ``replay_device`` — both execute the same per-element program.
    """
    from repro.kernels import ops
    mu, nu, count = opt.mu, opt.nu, opt.count
    up = _use_pallas()
    for _, payload in diffs:
        count = count + 1
        hyper = ops.adam_hyper_traced(lr, b1, b2, eps, count)
        params, mu, nu = _fused_step(params, mu, nu, hyper, payload, up)
    return params, AdamState(mu, nu, jnp.asarray(count, jnp.int32))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps"))
def _parallel_replay(params, mu0, nu0, stacked, count0, lr, *,
                     b1=0.9, b2=0.999, eps=1e-8):
    n = jax.tree.leaves(stacked)[0].shape[0]

    def scan_moments(g, m0, beta):
        # affine recurrence x_j = beta * x_{j-1} + (1-beta) g_j as an
        # associative scan over (a, b) pairs; a broadcast to g's shape.
        a = jnp.broadcast_to(
            jnp.full((n,) + (1,) * (g.ndim - 1), beta, jnp.float32),
            g.shape)
        aa, bb = jax.lax.associative_scan(
            lambda l, r: (l[0] * r[0], r[1] + r[0] * l[1]),
            (a, (1.0 - beta) * g))
        return bb + aa * m0                         # (n, ...) moments

    counts = count0 + 1 + jnp.arange(n)
    c1 = 1.0 - b1 ** counts.astype(jnp.float32)
    c2 = 1.0 - b2 ** counts.astype(jnp.float32)

    def one(p, g, m0, v0):
        mu_j = scan_moments(g, m0, b1)
        nu_j = scan_moments(g * g, v0, b2)
        cs = (1,) * (g.ndim - 1)
        step = lr * (mu_j / c1.reshape((n,) + cs)) / (
            jnp.sqrt(nu_j / c2.reshape((n,) + cs)) + eps)
        p2 = (p.astype(jnp.float32) - step.sum(0)).astype(p.dtype)
        return p2, mu_j[-1], nu_j[-1]

    out = jax.tree.map(one, params, stacked, mu0, nu0)
    p2 = jax.tree.map(lambda t: t[0], out,
                      is_leaf=lambda x: isinstance(x, tuple))
    mu2 = jax.tree.map(lambda t: t[1], out,
                       is_leaf=lambda x: isinstance(x, tuple))
    nu2 = jax.tree.map(lambda t: t[2], out,
                       is_leaf=lambda x: isinstance(x, tuple))
    return p2, mu2, nu2


def _decode_prefix(diffs: List[Tuple[int, Any]], p_leaves):
    """Host-decode payloads in order, stopping at the first corrupt one.
    Returns (dense grads for the longest decodable prefix, error or
    None) — ``contiguous_prefix`` semantics for *payload* corruption:
    a differential at position k that fails :func:`_check_payload`
    cuts the chain at k instead of raising mid-replay and losing the
    whole recovery. A failure of the decode itself (a kernel that does
    not compile or run) is not corruption and propagates."""
    gs = []
    for _, payload in diffs:
        try:
            _check_payload(payload, p_leaves)
        except CorruptDifferential as e:
            return gs, e
        gs.append(maybe_decompress(payload))
    return gs, None


def replay_parallel(params, opt: AdamState, diffs: List[Tuple[int, Any]], *,
                    lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                    window: Optional[int] = None):
    """Exact log-depth replay via associative scan over the moment
    recurrences. Numerically identical (up to reassociation) to serial.
    The jitted kernel is cached across calls (shapes keyed).

    ``window`` bounds peak memory: instead of materializing all n
    differentials as one dense fp32 stack — O(n · model) host/device
    bytes — the scan runs over windows of at most ``window``
    differentials, carrying ``(params, mu, nu, count)`` between them.
    The moment recurrences chain exactly across the boundary (each
    window's scan is seeded with the previous window's final moments),
    so the result is numerically identical up to the same float
    reassociation the unwindowed scan already accepts. ``None`` (or 0)
    replays everything in one window.

    Each window is host-decoded *before* its scan launches; a corrupt
    payload cuts the chain there — the state replayed so far is
    returned rather than thrown away — while a failing decode kernel
    propagates. Returns ``(params, opt, applied)`` with ``applied`` the
    number of
    differentials actually replayed (== ``len(diffs)`` when the whole
    chain was clean)."""
    from repro.checkpoint.io import COPY_METER
    if not diffs:
        return params, opt, 0
    if window is not None and window < 0:
        raise ValueError("window must be None or >= 0")
    w = int(window) if window else len(diffs)
    mu, nu, count = opt.mu, opt.nu, opt.count
    applied = 0
    for i in range(0, len(diffs), w):
        gs, err = _decode_prefix(diffs[i:i + w], jax.tree.leaves(params))
        if gs:
            stacked = jax.tree.map(lambda *xs: jnp.stack(
                [x.astype(jnp.float32) for x in xs]), *gs)
            COPY_METER.add_h2d(sum(l.nbytes
                                   for l in jax.tree.leaves(stacked)))
            params, mu, nu = _parallel_replay(params, mu, nu, stacked,
                                              count, jnp.float32(lr),
                                              b1=b1, b2=b2, eps=eps)
            count = count + len(gs)
            applied += len(gs)
        if err is not None:
            break
    return params, AdamState(mu, nu, count), applied


@functools.partial(jax.jit,
                   static_argnames=("b1", "b2", "eps", "use_pallas"))
def _device_replay(p_leaves, mu_leaves, nu_leaves, g_stacks, count0, lr, *,
                   b1=0.9, b2=0.999, eps=1e-8, use_pallas=False):
    """One jitted scan over a window of *compressed* payloads: each scan
    step slices one differential off the stacked wire buffers and runs
    the fused decompress-and-apply kernel per leaf — the dense gradient
    never exists outside the kernel accumulator. Leaf lists (not trees)
    because the payload containers are themselves pytree nodes."""
    from repro.kernels import ops

    def body(carry, gs):
        ps, mus, nus, c = carry
        c = c + 1
        hyper = ops.adam_hyper_traced(lr, b1, b2, eps, c)
        out = [ops.fused_decode_apply(g, p, m, v, hyper,
                                      use_pallas=use_pallas)
               for g, p, m, v in zip(gs, ps, mus, nus)]
        return ([o[0] for o in out], [o[1] for o in out],
                [o[2] for o in out], c), None

    init = (list(p_leaves), list(mu_leaves), list(nu_leaves),
            jnp.asarray(count0, jnp.int32))
    (p2, mu2, nu2, c2), _ = jax.lax.scan(body, init, tuple(g_stacks))
    return p2, mu2, nu2, c2


class CorruptDifferential(ValueError):
    """A stored differential whose wire containers do not describe the
    tensors they claim to — a torn or truncated write. Replay cuts the
    chain at such a differential; every other error propagates."""


def _check_payload(payload, p_leaves) -> None:
    """Cheap consistency check of a stored differential before any
    decode: one container (or dense array) per model leaf, each
    decoding to that leaf's shape, with a block-row count that matches
    the shape. The device path never materializes the dense form, so a
    truncated container would otherwise surface as a shape error deep
    inside the jitted scan instead of a clean chain cut. Raises
    :class:`CorruptDifferential`."""
    import numpy as np
    g_leaves = jax.tree.leaves(payload, is_leaf=_is_compressed)
    if len(g_leaves) != len(p_leaves):
        raise CorruptDifferential(
            f"differential has {len(g_leaves)} leaves, model has "
            f"{len(p_leaves)}")
    for g, p in zip(g_leaves, p_leaves):
        shape = tuple(g.shape if _is_compressed(g) else jnp.shape(g))
        if shape != tuple(p.shape):
            raise CorruptDifferential(
                f"differential leaf of shape {shape} for a model leaf of "
                f"shape {tuple(p.shape)}")
        if not _is_compressed(g):
            continue
        n = int(np.prod(shape)) if shape else 1
        nb = -(-n // g.block)               # ceil div
        lead = getattr(g, "values", None)
        lead = g.q if lead is None else lead
        if lead.shape[0] != nb:
            raise CorruptDifferential(
                f"corrupt differential: {lead.shape[0]} block rows for "
                f"shape {shape} (expected {nb})")


def _stage_window(diffs: List[Tuple[int, Any]], p_leaves):
    """H2D-stage a window's payloads in wire form. Uploads each
    differential's compressed buffers to the device (async
    ``device_put`` under the hood — the transfer overlaps whatever scan
    is already running) and stacks them along a leading axis for
    ``lax.scan``. A corrupt payload — torn containers, leaves that do
    not match the model, or a structure change mid-window — cuts the
    window there (``contiguous_prefix`` semantics); a failing upload
    propagates. Returns ``(stacked | None, n_staged, error | None)``."""
    from repro.checkpoint.io import COPY_METER
    from repro.obs.trace import trace_span
    with trace_span("replay.h2d", "recovery", n=len(diffs)) as sp:
        staged, err, template = [], None, None
        nbytes = 0
        for _, payload in diffs:
            try:
                _check_payload(payload, p_leaves)
                tdef = jax.tree.structure(payload)
                if template is None:
                    template = tdef
                elif tdef != template:
                    raise CorruptDifferential(
                        "differential structure changed mid-window")
            except CorruptDifferential as e:
                err = e
                break
            dev = jax.tree.map(jnp.asarray, payload)
            nbytes += sum(l.nbytes for l in jax.tree.leaves(dev))
            staged.append(dev)
        if not staged:
            return None, 0, err
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *staged)
        COPY_METER.add_h2d(nbytes)
        sp.set(bytes=nbytes, staged=len(staged))
        return stacked, len(staged), err


def replay_device(params, opt: AdamState, diffs: List[Tuple[int, Any]], *,
                  lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                  window: Optional[int] = None,
                  use_pallas: Optional[bool] = None):
    """Device-resident serial-exact replay: payloads cross the
    interconnect compressed (ρ·dense bytes instead of dense fp32), and
    one jitted scan per window decodes-and-applies them with the fused
    kernels. Bit-identical to :func:`replay_serial` — same per-element
    program, different orchestration.

    Windows are double-buffered: window N's scan is dispatched
    asynchronously, then window N+1's payloads stage H2D while it runs.
    A corrupt payload (:class:`CorruptDifferential`, found before
    dispatch) cuts the chain at that diff; a compile or runtime error
    of the replay program propagates. Returns ``(params, opt,
    applied)``."""
    if not diffs:
        return params, opt, 0
    if window is not None and window < 0:
        raise ValueError("window must be None or >= 0")
    w = int(window) if window else len(diffs)
    up = _use_pallas() if use_pallas is None else use_pallas
    p_leaves, treedef = jax.tree.flatten(params)
    mu_l = jax.tree.leaves(opt.mu)
    nu_l = jax.tree.leaves(opt.nu)
    count = jnp.asarray(opt.count, jnp.int32)
    applied = 0
    windows = [diffs[i:i + w] for i in range(0, len(diffs), w)]
    nxt = _stage_window(windows[0], p_leaves)
    for i in range(len(windows)):
        stacked, n, err = nxt
        if n:
            g_stacks = jax.tree.leaves(stacked, is_leaf=_is_compressed)
            p_leaves, mu_l, nu_l, count = _device_replay(
                p_leaves, mu_l, nu_l, g_stacks, count,
                jnp.float32(lr), b1=b1, b2=b2, eps=eps, use_pallas=up)
            applied += n
        if err is not None:
            break
        if i + 1 < len(windows):
            # double buffer: the scan above was dispatched async; the
            # next window's (compressed, hence small) H2D runs under it
            nxt = _stage_window(windows[i + 1], p_leaves)
    return (jax.tree.unflatten(treedef, p_leaves),
            AdamState(jax.tree.unflatten(treedef, mu_l),
                      jax.tree.unflatten(treedef, nu_l), count),
            applied)


# ---------------- device-resident patch-chain overlay ----------------

def overlay_device(state, updates, *, use_pallas: Optional[bool] = None):
    """Device-side twin of :func:`repro.checkpoint.store.merge_updates`
    for patch blobs: nested dicts merge, a quantized
    :class:`~repro.compression.quant_span.QuantSpan` leaf is
    dequantized-and-scattered into the state leaf by the fused
    ``quant_span_apply`` kernel (no host decode of the wire bytes), a
    raw :class:`RowUpdate` splices on host, anything else replaces.
    Mutates ``state`` in place; overlaid leaves come back as numpy.
    Bit-identical to the host overlay: the kernel performs the same f32
    dequant ops as the host codec."""
    import numpy as np

    from repro.checkpoint.io import COPY_METER
    from repro.checkpoint.patchset import RowUpdate
    from repro.compression.quant_span import QuantSpan
    from repro.kernels import ops
    up = _use_pallas() if use_pallas is None else use_pallas
    for k, v in updates.items():
        if isinstance(v, dict) and isinstance(state.get(k), dict):
            overlay_device(state[k], v, use_pallas=up)
        elif isinstance(v, QuantSpan):
            dst = jnp.asarray(np.asarray(state[k]))
            for start, q, sc in zip(v.starts, v.qs, v.scales):
                COPY_METER.add_h2d(q.nbytes + sc.nbytes)
                dst = ops.fused_span_apply(dst, int(start),
                                           jnp.asarray(q),
                                           jnp.asarray(sc),
                                           bits=v.bits, use_pallas=up)
            state[k] = np.asarray(dst)
        elif isinstance(v, RowUpdate):
            base = np.array(state[k])
            for sp in v.spans():
                base[sp.start:sp.stop] = sp.data
            state[k] = base
        else:
            state[k] = v


def load_state_device(store, *, use_pallas: Optional[bool] = None):
    """Hardware-recovery twin of ``store.load_latest_state`` that
    overlays the patch chain on device: quantized span payloads upload
    in wire form (1/4 to 1/8 of the raw span bytes over the
    interconnect) and the fused ``quant_span_apply`` kernel scatters
    the dequantized rows straight into the state leaf. Same fallback /
    chain-cut semantics as the host path, and bit-identical output.
    Returns ``(state, step)``."""
    from repro.checkpoint.io import FrameCorruptionError
    from repro.checkpoint.remote import RetryExhaustedError
    from repro.checkpoint.store import order_fulls
    with store._lock:
        fulls = order_fulls(store.manifest["fulls"])
    if not fulls:
        raise FileNotFoundError("no persisted checkpoint")
    last_err = None
    for entry in fulls:
        try:
            state = store.load_full(entry)
        except (FileNotFoundError, RetryExhaustedError,
                FrameCorruptionError) as e:
            last_err = e
            continue
        step = int(entry.get("state_step", entry["step"]))
        for pe in store.patch_chain(store._entry_key(entry)):
            try:
                blob = store.backend.get(store._entry_key(pe))
            except (FileNotFoundError, RetryExhaustedError,
                    FrameCorruptionError):
                break            # cut at the gap: prefix is committed
            overlay_device(state, blob["updates"], use_pallas=use_pallas)
            step = max(step, int(pe["step"]))
        return state, step
    raise FileNotFoundError(
        f"none of {len(fulls)} full checkpoints is loadable "
        f"(last error: {last_err})")


def merge_deltas_pairwise(deltas: List[Any]) -> Any:
    """Paper's literal pairwise tree merge for *state-delta* differentials
    (Naïve DC): log2(n) rounds of pairwise sums."""
    deltas = list(deltas)
    rounds = 0
    while len(deltas) > 1:
        nxt = []
        for i in range(0, len(deltas) - 1, 2):
            nxt.append(jax.tree.map(lambda a, b: a + b,
                                    deltas[i], deltas[i + 1]))
        if len(deltas) % 2:
            nxt.append(deltas[-1])
        deltas = nxt
        rounds += 1
    return deltas[0], rounds
