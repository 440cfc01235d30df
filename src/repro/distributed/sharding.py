"""Per-arch PartitionSpec rules (DP / FSDP / TP / EP / SP).

The mesh has axes (data, model) per pod, plus a leading ``pod`` axis in the
multi-pod configuration.  Data parallelism runs over (pod, data); tensor
parallelism over ``model``; experts (EP) shard their leading expert axis
over ``model``; FSDP additionally shards large parameter matrices over the
data axes (required for deepseek-v3 / qwen1.5-32b / gemma2-27b).

Rules are name-based over the flattened param tree — auditable with
``describe_shardings``.  GSPMD handles non-divisible dims by padding, so
rules do not need divisibility checks.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.mask_pack import ops as mask_ops
from repro.kernels.mask_pack.kernel import BLOCK


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_spec(cfg, mesh: Mesh, name: str, leaf) -> P:
    """PartitionSpec for one parameter leaf (name = '/'-joined path)."""
    dp = data_axes(mesh)
    fs = dp if cfg.fsdp else None  # FSDP shard axis group (or None)
    nd = len(leaf.shape)
    last = name.rsplit("/", 1)[-1]
    has_stack = "segments" in name or "blocks" in name  # leading scan dim

    def spec(*dims):
        """dims for the *logical* (unstacked) shape; prepend None if stacked."""
        if has_stack:
            return P(*((None,) + dims))
        return P(*dims)

    logical_nd = nd - 1 if has_stack else nd

    # --- embeddings / head ---
    if name == "embed":
        return P("model", fs)              # vocab over TP, d over FSDP
    if name == "lm_head":
        return P(fs, "model")

    # --- norms / scalars ---
    if last in ("scale", "bias", "lambda") or logical_nd <= 1:
        return spec(*(None,) * logical_nd)

    # --- MoE experts: EP over the expert axis ---
    if "/moe/" in name or name.endswith("/moe"):
        if last == "router":
            return spec(None, None)
        if "shared" in name:
            if last in ("wi", "wg"):
                return spec(None, fs, "model")
            return spec(None, "model", fs)
        if last in ("wi", "wg"):       # (E, d, f)
            return spec("model", fs, None)
        if last == "wo":               # (E, f, d)
            return spec("model", None, fs)

    # --- attention / mixers ---
    if last in ("wq", "wk", "wv", "wz", "wi", "wf", "wg",
                "wq_b", "wk_b", "wv_b", "w_in", "w_gate"):
        return spec(fs, "model")           # column parallel
    if last in ("wo", "w_out"):
        return spec("model", fs)           # row parallel
    if last in ("wq_a", "wkv_a"):
        return spec(fs, None)              # low-rank down-proj (small out dim)
    if last in ("bq", "bk", "bv"):
        return spec("model")
    if last == "conv":
        return spec(None, "model")
    if last in ("w_a", "w_x"):             # (r, r) LRU gates
        return spec(None, "model")
    if last in ("rz", "ri", "rf", "ro"):   # (H, hd, hd) sLSTM recurrent
        return spec("model", None, None)

    return spec(*(None,) * logical_nd)


def fit_spec(mesh: Mesh, spec: P, shape) -> P:
    """Drop axes whose size does not divide the dim (jit in_shardings
    require exact divisibility).  Handles tuple axis entries by keeping the
    longest divisible prefix of the group."""
    dims = list(spec)
    dims = dims + [None] * (len(shape) - len(dims))
    out = []
    for d, n in zip(dims, shape):
        if d is None:
            out.append(None)
            continue
        axes = d if isinstance(d, tuple) else (d,)
        kept = []
        prod = 1
        for a in axes:
            sz = mesh.shape[a]
            if n % (prod * sz) == 0:
                kept.append(a)
                prod *= sz
            else:
                break
        out.append(tuple(kept) if len(kept) > 1 else
                   (kept[0] if kept else None))
    return P(*out)


def params_shardings(cfg, mesh: Mesh, params_shape) -> Any:
    """Map a params pytree (arrays or ShapeDtypeStructs) to NamedShardings."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    out = []
    for path, leaf in flat:
        p = param_spec(cfg, mesh, _path_str(path), leaf)
        out.append(NamedSharding(mesh, fit_spec(mesh, p, leaf.shape)))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_shardings(cfg, mesh: Mesh, batch_shape, *, seq_shard: bool = False):
    """Batch dim over (pod, data); optional SP shards the seq dim over
    ``model`` (long-context training)."""
    dp = data_axes(mesh)
    sp = "model" if seq_shard else None

    def one(path, leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return NamedSharding(mesh, P())
        if nd == 1:
            spec = P(None)
        elif nd == 2:   # (B, T)
            spec = P(dp, sp)
        else:           # (B, T, d) stub embeddings / (B, T, 3) positions
            spec = P(dp, sp, *(None,) * (nd - 2))
        return NamedSharding(mesh, fit_spec(mesh, spec, leaf.shape))

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch_shape)
    return jax.tree_util.tree_unflatten(
        treedef, [one(p, l) for p, l in flat])


def cache_shardings(cfg, mesh: Mesh, cache_shape):
    """KV caches: batch over (pod, data), heads/latent dim over model."""
    dp = data_axes(mesh)

    def one(path, leaf):
        name = _path_str(path)
        last = name.rsplit("/", 1)[-1]
        nd = len(leaf.shape)
        # stacked over a leading scan dim inside segments
        stacked = "seg" in name
        pre = (None,) if stacked else ()
        lnd = nd - len(pre)
        if last in ("k", "v", "xk", "xv") and lnd == 4:   # (B,S,K,hd)
            spec = P(*pre, dp, None, "model", None)
        elif last in ("c_kv", "k_pe") and lnd == 3:       # (B,S,R) MLA latent
            spec = P(*pre, dp, None, None)
        elif last == "C" and lnd == 4:                    # (B,H,hd,hd)
            spec = P(*pre, dp, "model", None, None)
        elif lnd >= 2:
            spec = P(*pre, dp, *(None,) * (lnd - 1))
        else:
            spec = P(*pre, *(None,) * lnd)
        return NamedSharding(mesh, fit_spec(mesh, spec, leaf.shape))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(
        treedef, [one(p, l) for p, l in flat])


# --------------------------------------------------------------------------
# Scrutinized checkpoint save path: pack per shard *before* any gather.
# --------------------------------------------------------------------------

def _as_flat_mask(mask):
    """Flat view of a criticality mask without forcing a host round-trip:
    resident device masks (a ``DeviceReport``'s) stay on device — the whole
    point of the device scrutiny engine is that saves never re-upload the
    mask — while host numpy masks keep the original behaviour."""
    if isinstance(mask, jax.Array):
        return jnp.ravel(mask)
    return np.asarray(mask).reshape(-1)


def _mask_segment(mask, lo: int, hi: int, data):
    """Slice ``mask[lo:hi]`` for one leading-axis shard, colocated with the
    shard's ``data`` when the mask is a device array (a sharded/resident
    mask's slice may live elsewhere; jitted pack rejects mixed devices)."""
    seg = mask[lo:hi]
    if isinstance(mask, jax.Array):
        seg = jax.device_put(seg, next(iter(data.devices())))
    return seg


def scrutiny_words_shardings(state, shardings) -> Dict[str, Any]:
    """Per-leaf shardings for the scrutiny engine's bit-packed mask words.

    For every leaf whose sharding tiles only the leading axis into
    byte-aligned flat segments (the DP/FSDP parameter layouts that
    ``pack_sharded_payload_device`` packs per shard), the flat word array
    ``(ceil(n/8),)`` can carry the same leading-axis spec — per-shard mask
    words then land on the device whose shard they describe.  Leaves with
    any other layout map to ``None`` (words stay wherever the sweep puts
    them).  Feed the result to ``scrutinize(..., mask_shardings=...)``.
    """
    flat_t = jax.tree_util.tree_flatten_with_path(state)[0]
    # None entries mean "no sharding for this leaf" and must stay leaves
    # (bare tree_leaves would silently drop them and misalign the zip)
    flat_s = jax.tree_util.tree_leaves(
        shardings,
        is_leaf=lambda x: x is None or isinstance(x, NamedSharding))
    out: Dict[str, Any] = {}
    for (path, leaf), sh in zip(flat_t, flat_s):
        name = _path_str(path)
        out[name] = None
        if not isinstance(sh, NamedSharding) or not len(leaf.shape):
            continue
        spec = tuple(sh.spec) + (None,) * (len(leaf.shape) - len(sh.spec))
        if spec[0] is None or any(d is not None for d in spec[1:]):
            continue
        axes = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
        nshards = int(np.prod([sh.mesh.shape[a] for a in axes]))
        row = int(np.prod(leaf.shape[1:])) if len(leaf.shape) > 1 else 1
        if nshards <= 1 or leaf.shape[0] % nshards:
            continue
        if (leaf.shape[0] // nshards * row) % 8:
            continue  # shard boundary splits a word byte: keep replicated
        out[name] = NamedSharding(sh.mesh, P(spec[0]))
    return out

def _leading_axis_shards(leaf) -> Optional[List[Tuple[int, int, Any]]]:
    """If ``leaf``'s addressable shards tile only the leading axis (all other
    dims full), return [(start, stop, shard_data)] sorted and exactly covering
    axis 0; else None.  Replicated copies are deduplicated."""
    shards = getattr(leaf, "addressable_shards", None)
    if not shards or leaf.ndim == 0:
        return None
    uniq: Dict[int, Any] = {}
    stops: Dict[int, int] = {}
    for sh in shards:
        idx = sh.index
        if len(idx) != leaf.ndim:
            return None
        for d, sl in enumerate(idx[1:], start=1):
            if sl.step not in (None, 1):
                return None
            if sl.start not in (None, 0):
                return None
            if sl.stop is not None and sl.stop != leaf.shape[d]:
                return None
        sl0 = idx[0]
        if sl0.step not in (None, 1):
            return None
        s = sl0.start or 0
        e = leaf.shape[0] if sl0.stop is None else sl0.stop
        uniq.setdefault(s, sh.data)
        stops[s] = e
    starts = sorted(uniq)
    if starts[0] != 0 or stops[starts[-1]] != leaf.shape[0]:
        return None
    for a, b in zip(starts, starts[1:]):
        if stops[a] != b:
            return None
    return [(s, stops[s], uniq[s]) for s in starts]


def device_segments(leaf) -> Tuple[List[Tuple[int, int, Any]], bool]:
    """Row ranges of a fully addressable leaf that spans several devices,
    each held by one device: ``([(row_start, row_stop, data)], retiled)``
    with the ranges in order, exactly covering axis 0.

    A leading-axis layout (DP/FSDP) answers with its own shards and moves
    nothing.  Any other layout (a model-axis split, a 2-D split) is
    re-tiled (``retiled=True``): the rows are split evenly over the leaf's
    devices and each range is moved onto its device, so every device ends
    up with about ``1/n`` of the leaf and none holds all of it.  A Pallas
    kernel runs on one device's data, so this is how a sharded leaf
    reaches the pack kernel."""
    segs = _leading_axis_shards(leaf)
    if segs is not None:
        return segs, False
    if leaf.ndim == 0:          # a 0-d leaf on several devices is replicated
        return [(0, 1, leaf.addressable_shards[0].data)], False
    devs = sorted(leaf.devices(), key=lambda d: d.id)
    k = min(len(devs), leaf.shape[0])
    cuts = [leaf.shape[0] * i // k for i in range(k + 1)]
    return ([(a, b, jax.device_put(leaf[a:b], d))
             for a, b, d in zip(cuts, cuts[1:], devs)], True)


def _pack_payload_device(flat, mask, *, block: int = BLOCK,
                         use_kernel: Optional[bool] = None,
                         interpret: bool = False):
    """Pack one flat leaf's critical elements, keeping the payload on
    device.  Returns (payload_dev, counts_h, d2h_bytes) — only the per-tile
    counts cross D2H here; the payload moves (or is delta-encoded) later."""
    packed, counts = mask_ops.pack(flat, jnp.asarray(mask), block=block,
                                   use_kernel=use_kernel, interpret=interpret)
    counts_h = np.asarray(counts)                  # D2H: 4 B / tile
    total = int(counts_h.sum())
    if total:
        payload = mask_ops.gather_payload(packed, counts, total=total)
    else:
        payload = packed.reshape(-1)[:0]
    return payload, counts_h, counts_h.nbytes


def pack_sharded_payload_device(leaf, mask, *, block: int = BLOCK,
                                use_kernel: Optional[bool] = None,
                                interpret: bool = False):
    """Pack a (possibly sharded) device leaf's critical elements, keeping
    the payload on device.  A leaf on several devices is packed **per
    device** over :func:`device_segments` (no device ever gathers the full
    leaf), then the (already critical-fraction-sized) payloads are
    concatenated into one device array that stays resident as the delta
    base — only the per-tile counts cross D2H.  The payload is in global
    flat (C) order, identical bytes to the host path.

    Returns ``(payload_dev, counts_h, d2h_bytes, retiled)``; ``retiled``
    says whether the leaf's layout had to be re-tiled into row ranges.
    The concatenation gathers the *packed* payloads onto one device, so
    cross-device traffic there is ∝ the critical fraction.  A resident
    device mask is consumed without any host round-trip.
    """
    mask = _as_flat_mask(mask)
    if len(leaf.devices()) == 1 or not leaf.is_fully_addressable:
        return _pack_payload_device(jnp.ravel(leaf), mask, block=block,
                                    use_kernel=use_kernel,
                                    interpret=interpret) + (False,)
    segs, retiled = device_segments(leaf)
    row = int(np.prod(leaf.shape[1:])) if leaf.ndim > 1 else 1
    payloads, counts, moved = [], [], 0
    for s, e, data in segs:
        p, c, m = _pack_payload_device(
            jnp.ravel(data), _mask_segment(mask, s * row, e * row, data),
            block=block, use_kernel=use_kernel, interpret=interpret)
        payloads.append(p)
        counts.append(c)
        moved += m
    # co-locate the packed (critical-fraction-sized) payloads before the
    # concat — committed arrays on different devices refuse to mix lazily
    home = payloads[0].devices()
    payloads = [p if p.devices() == home else jax.device_put(p, list(home)[0])
                for p in payloads]
    return (jnp.concatenate(payloads), np.concatenate(counts), moved,
            retiled)


# --------------------------------------------------------------------------
# Scrutinized restore path: scatter per shard *after* a payload-only H2D.
# --------------------------------------------------------------------------

def _leading_axis_segments(sharding, shape
                           ) -> Optional[List[Tuple[int, int, Any]]]:
    """Per-device leading-axis segments of a target ``sharding`` over a
    global ``shape``: [(start, stop, device)], one entry per addressable
    device (replicas repeat their segment); None if the layout slices any
    non-leading dim."""
    if not shape or not hasattr(sharding, "addressable_devices_indices_map"):
        return None
    try:
        idx_map = sharding.addressable_devices_indices_map(tuple(shape))
    except (TypeError, ValueError):
        return None
    out = []
    for dev, idx in idx_map.items():
        if idx is None or len(idx) != len(shape):
            return None
        for d, sl in enumerate(idx[1:], start=1):
            if sl.step not in (None, 1) or sl.start not in (None, 0):
                return None
            if sl.stop is not None and sl.stop != shape[d]:
                return None
        sl0 = idx[0]
        if sl0.step not in (None, 1):
            return None
        s = sl0.start or 0
        e = shape[0] if sl0.stop is None else sl0.stop
        out.append((s, e, dev))
    return out


def leading_axis_device_segments(sharding, shape
                                 ) -> Optional[List[Tuple[int, int, Any]]]:
    """Public wrapper over the leading-axis layout parser for consumers
    outside the scatter path (the multi-host coordinator derives both
    save-time ownership and restore-time target ranges from it):
    per-device ``[(row_start, row_stop, device)]`` of ``sharding`` over a
    global ``shape``, or None when the layout slices a non-leading dim."""
    return _leading_axis_segments(sharding, shape)


def scatter_sharded_payload(payload: np.ndarray, mask: np.ndarray,
                            shape, dtype, sharding=None, *, fill=0,
                            block: int = BLOCK,
                            use_kernel: Optional[bool] = None,
                            interpret: bool = False):
    """Restore inverse of :func:`pack_sharded_payload_device`: move only the
    critical ``payload`` (plus the bit-packed mask) H2D and scatter it into
    a fill-initialized device buffer via ``kernels/mask_pack``.

    When ``sharding`` tiles only the leading axis, each device receives and
    expands just its own segment's slice of the payload — restore traffic
    per device scales with its local critical fraction; the global array is
    assembled from the single-device pieces without any host round-trip.

    Returns ``(device_array, h2d_bytes)``.
    """
    shape = tuple(shape)
    n = int(np.prod(shape)) if shape else 1
    mask = np.asarray(mask, bool).reshape(-1)
    payload = np.asarray(payload).reshape(-1)
    opts = dict(block=block, use_kernel=use_kernel, interpret=interpret)

    def expand(pay_h, msk_h, local_n, device=None):
        bits = np.packbits(msk_h)
        put = (lambda x: jax.device_put(x, device)) if device is not None \
            else jnp.asarray
        m_dev = mask_ops.expand_mask_bits(put(bits), n=local_n)
        out = mask_ops.mask_scatter(put(pay_h), m_dev, n=local_n,
                                    fill=fill, **opts)
        return out, pay_h.nbytes + bits.nbytes

    segs = _leading_axis_segments(sharding, shape) if sharding is not None \
        else None
    if segs is None:
        out, h2d = expand(payload, mask, n)
        out = out.reshape(shape)
        if sharding is not None:
            out = jax.device_put(out, sharding)
        return out, h2d

    row = int(np.prod(shape[1:])) if len(shape) > 1 else 1
    cum = np.concatenate([[0], np.cumsum(mask)])
    pieces, h2d = [], 0
    for s, e, dev in segs:
        lo, hi = cum[s * row], cum[e * row]
        piece, moved = expand(payload[lo:hi], mask[s * row:e * row],
                              (e - s) * row, device=dev)
        pieces.append(piece.reshape((e - s,) + shape[1:]))
        h2d += moved
    out = jax.make_array_from_single_device_arrays(shape, sharding, pieces)
    return out, h2d


def describe_shardings(cfg, mesh: Mesh, tree, shardings, limit=40) -> str:
    flat_t = jax.tree_util.tree_flatten_with_path(tree)[0]
    flat_s = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    lines = []
    for (path, leaf), sh in list(zip(flat_t, flat_s))[:limit]:
        lines.append(f"{_path_str(path):<60} {str(leaf.shape):<24} {sh.spec}")
    return "\n".join(lines)
