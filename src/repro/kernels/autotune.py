"""Block-size selection for the Pallas kernels: shape-keyed cache over
cost-model-informed defaults.

Every kernel wrapper asks ``select_blocks(op, shape, dtype)`` for its block
sizes when the caller does not pin them.  Resolution order:

  1. the process-local cache (previous ``select_blocks`` result for the same
     (op, shape, dtype) key, or an explicit ``register`` from a measured
     ``tune`` sweep), then
  2. an analytic default that maximizes MXU-aligned tiles under a VMEM
     working-set budget (the dominant constraint on real TPUs: x-tile +
     w-tile + accumulator must co-reside in ~16 MB/core VMEM).

``tune(op, fn, candidates, *args)`` optionally times candidate block dicts
(interpret mode on CPU, native on TPU) and registers the winner — used by
``benchmarks/kernel_bench.py``; the serving path only ever pays the cheap
analytic default plus one dict lookup per (shape, dtype).

Persistence: measured registrations (``register`` / ``tune``) are written
through to a versioned JSON under ``~/.cache/repro/autotune.json``
(override with ``REPRO_AUTOTUNE_CACHE``; set it to an empty string to
disable).  ``select_blocks`` loads the file lazily on the first in-memory
miss, so a ``tune`` sweep in one process benefits every later process.  A
version mismatch (the block-dict schema changed) silently invalidates the
whole file — stale overrides are worse than the analytic default.
Analytic defaults are never persisted (they are free to recompute).
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import warnings
from typing import Callable, Optional

import numpy as _np

# f32 working-set budget per grid step; conservative half of the ~16 MB/core
# VMEM so double-buffered pipelining of the next tiles fits alongside.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024
# the scoped VMEM a kernel may use on a v5e core unless it asks for more
SCOPED_VMEM_BYTES = 2 * VMEM_BUDGET_BYTES
_MXU = 128


@dataclasses.dataclass(frozen=True)
class KernelKey:
    op: str           # "moe_gemm" | "permute" | "unpermute" | "topk_gate" | ...
    shape: tuple      # the op-defining dims (see each kernel's wrapper)
    dtype: str


_CACHE: dict[KernelKey, dict] = {}

# ---------------------------------------------------------------------------
# Cross-process persistence (measured registrations only)
# ---------------------------------------------------------------------------

# v2: added the "flash_chunk" op key ({bq, bs} block dicts) — v1 files
# predate the ragged mixed-chunk kernel and are invalidated wholesale.
# v3: added "flash_chunk_paged" (bs must divide the KV page size, so its
# defaults differ from flash_chunk's) — v2 files invalidated wholesale.
# v4: added "kv_page" ({page} dicts — the paged-KV page size feeds the
# flash_chunk_paged tile constraint) — v3 files invalidated wholesale.
# v5: added "resolver" ({batch_cap, itl_slack_pct} — the per-host serving
# resolver constants measured by the kernel_bench calibration) — v4 files
# invalidated wholesale.
CACHE_VERSION = 5
_persist_loaded = False


def cache_path() -> Optional[str]:
    """Resolved persistent-cache path, or None when disabled."""
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env is not None:
        return env or None            # "" disables persistence
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


def _key_str(key: KernelKey) -> str:
    return f"{key.op}|{','.join(map(str, key.shape))}|{key.dtype}"


def _key_from_str(s: str) -> Optional[KernelKey]:
    try:
        op, shape, dtype = s.split("|")
        return KernelKey(op=op,
                         shape=tuple(int(x) for x in shape.split(",") if x),
                         dtype=dtype)
    except ValueError:
        return None


def _read_persistent() -> dict:
    """{key_str: blocks} from disk; {} on any problem or version skew."""
    path = cache_path()
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
    if payload.get("version") != CACHE_VERSION:
        return {}                     # stale schema: ignore wholesale
    entries = payload.get("entries")
    return entries if isinstance(entries, dict) else {}


def load_persistent() -> int:
    """Merge the on-disk registrations into the in-memory cache (in-memory
    entries win).  Idempotent per process; returns entries adopted."""
    global _persist_loaded
    _persist_loaded = True
    adopted = 0
    for ks, blocks in _read_persistent().items():
        key = _key_from_str(ks)
        if key is None or key in _CACHE or not isinstance(blocks, dict):
            continue
        _CACHE[key] = {k: int(v) for k, v in blocks.items()}
        adopted += 1
    return adopted


def _write_persistent(key: KernelKey, blocks: dict) -> None:
    path = cache_path()
    if not path:
        return
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        entries = _read_persistent()
        entries[_key_str(key)] = {k: int(v) for k, v in blocks.items()}
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                   suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": entries}, f,
                      indent=1)
        os.replace(tmp, path)         # atomic vs concurrent writers
    except OSError:
        pass                          # persistence is best-effort


def cache_key(op: str, shape: tuple, dtype) -> KernelKey:
    # normalize through np.dtype so jnp.float32 (a type), an np.dtype and
    # the string "float32" all land on the same key — a register() with
    # the type object must be found by the serving path's x.dtype lookup
    try:
        dtype = _np.dtype(dtype)
    except TypeError:
        pass
    return KernelKey(op=op, shape=tuple(int(s) for s in shape),
                     dtype=str(dtype))


def register(op: str, shape: tuple, dtype, blocks: dict, *,
             persist: bool = True) -> None:
    key = cache_key(op, shape, dtype)
    _CACHE[key] = dict(blocks)
    if persist:
        _write_persistent(key, blocks)


def cache_info() -> dict:
    """Snapshot of the cache (tests / benchmarks introspection)."""
    return {k: dict(v) for k, v in _CACHE.items()}


def clear_cache(*, persistent: bool = False) -> None:
    global _persist_loaded
    _CACHE.clear()
    _persist_loaded = False           # allow a fresh lazy load
    if persistent:
        path = cache_path()
        if path and os.path.exists(path):
            os.remove(path)


def _bytes(dtype: str) -> int:
    return 2 if "bfloat16" in dtype or "float16" in dtype else 4


def _fit(dim: int, cap: int, align: int = _MXU) -> int:
    """Largest block <= cap covering dim, MXU-aligned once past ``align``."""
    if dim <= align:
        return max(1, dim)
    b = min(cap, dim)
    return max(align, (b // align) * align)


def grouped_gemm_vmem(bn: int, bh: int, bd: int, el: int) -> int:
    """VMEM one ``grouped_gemm`` grid step holds as compiled: the x, w and
    out blocks double-buffered, the f32 accumulator and the dot's f32
    result, and the masked copy of the x block."""
    x, w, out, acc = bn * bh * el, bh * bd * el, bn * bd * el, bn * bd * 4
    return 2 * (x + w + out) + 2 * acc + x


def _tiles(dim: int, align: int = _MXU) -> list:
    """Candidate tiles for ``dim``, largest first: the MXU-aligned tiles
    that divide it (the whole dim when it is at most one MXU width), or,
    where no multiple of ``align`` divides it, ``_fit``'s tile and its
    halvings, which the kernel wrapper pads up to."""
    if dim <= align:
        return [max(1, dim)]
    tiles = [b for b in range(dim - dim % align, 0, -align) if dim % b == 0]
    if tiles:
        return tiles
    b = _fit(dim, 512, align)
    tiles = [b]
    while b > align:
        b = max(align, (b // 2) // align * align)
        tiles.append(b)
    return tiles


def _default_blocks(op: str, shape: tuple, dtype: str) -> dict:
    el = _bytes(dtype)
    if op == "moe_gemm":
        _, c, h, d = shape
        bc, bh, bd = _fit(c, 512), _fit(h, 512), _fit(d, 512)
        # shrink the largest tile until x(bc,bh) + w(bh,bd) + acc(bc,bd) fits
        while (bc * bh * el + bh * bd * el + bc * bd * 4) > VMEM_BUDGET_BYTES:
            m = max(bc, bh, bd)
            if m <= _MXU:
                break
            if bc == m:
                bc = max(_MXU, bc // 2)
            elif bh == m:
                bh = max(_MXU, bh // 2)
            else:
                bd = max(_MXU, bd // 2)
        return {"bc": bc, "bd": bd, "bh": bh}
    if op == "grouped_gemm":
        # tiles that divide H and D, so the weights are read where they lie
        # with no pad.  bh first: each grid step adds its (bn, bd) dot into
        # the f32 accumulator and masks its (bn, bh) x block, so the
        # accumulator's VMEM traffic per MXU pass falls as 1/bh and the
        # mask's as 1/bd.  bh is the widest that fits beside a bd of at
        # least 512 (or D's widest tile, if smaller); bd then grows into
        # what is left.  bn stays at most 512: a smaller one also means
        # fewer masked rows per boundary-straddling visit.
        n, h, d, _e = shape
        bn = _fit(n, 512)
        limit = SCOPED_VMEM_BYTES - SCOPED_VMEM_BYTES // 8

        def fits(bh, bd):
            return grouped_gemm_vmem(bn, bh, bd, el) <= limit
        hs, ds = _tiles(h), _tiles(d)
        bd_min = min(b for b in ds if b >= min(512, ds[0]))
        bh = next((b for b in hs if fits(b, bd_min)), hs[-1])
        bd = next((b for b in ds if fits(bh, b)), ds[-1])
        return {"bn": bn, "bd": bd, "bh": bh}
    if op in ("permute", "unpermute"):
        # n output rows per grid step; the gather source stays in HBM (rows
        # arrive by DMA), so VMEM holds only the tile's rows: the zero tile
        # (permute) or the k fetched rows plus the output tile (unpermute).
        n, h = shape[0], shape[1]
        bn = 8
        while bn * 2 <= n and bn * 2 * h * el <= VMEM_BUDGET_BYTES // 8:
            bn *= 2
        return {"bn": min(bn, 512)}
    if op == "topk_gate":
        t, e = shape
        bt = 8
        while bt * 2 <= t and bt * 2 * e * 4 <= VMEM_BUDGET_BYTES // 8:
            bt *= 2
        return {"bt": min(bt, 1024)}
    if op == "flash_decode":
        s = shape[1]          # key is k.shape = (B, S, nkv, hd)
        bs = 128
        while bs * 2 <= s and bs <= 1024:
            bs *= 2
        return {"bs": min(bs, 2048)}
    if op == "flash_chunk":
        # key is q.shape + (S,) = (B, sq, nq, hd, S): the q tile covers the
        # chunk (it is small — the token-budget chunk width), the S tile
        # grows with the cache like flash_decode's
        _b, sq, _nq, _hd, s = shape
        bq = 8
        while bq * 2 <= sq and bq <= 64:
            bq *= 2
        bs = 128
        while bs * 2 <= s and bs <= 1024:
            bs *= 2
        return {"bq": min(bq, 128), "bs": min(bs, 2048)}
    if op == "kv_page":
        # key is (max_len, kv_row_els): paged-KV page size.  Not a Pallas
        # tile — it sizes the cache pages the flash_chunk_paged block table
        # routes, so the analytic default is the serving-tier constant (16)
        # degraded to divide the length envelope; a measured ``tune`` sweep
        # (benchmarks/kernel_bench.py) overrides it per shape.
        max_len = shape[0]
        page = 16
        while page > 1 and max_len % page:
            page //= 2
        return {"page": max(page, 1)}
    if op == "flash_chunk_paged":
        # key is q.shape + (P, page) = (B, sq, nq, hd, P, page): q tile as
        # flash_chunk; the KV tile must DIVIDE the page size (the block
        # table routes whole tiles), so take the largest power-of-two
        # divisor of the page up to the flash_chunk cap
        _b, sq, _nq, _hd, _p, page = shape
        bq = 8
        while bq * 2 <= sq and bq <= 64:
            bq *= 2
        bs = 1
        while bs * 2 <= min(page, 2048) and page % (bs * 2) == 0:
            bs *= 2
        if page % bs:                 # odd page size: one tile per page
            bs = page
        return {"bq": min(bq, 128), "bs": bs}
    if op == "resolver":
        # key is (): per-host serving-resolver constants (the cache file is
        # already per-host).  These are the ANALYTIC defaults mirrored from
        # core.resolve — resolved_batch_cap/resolved_itl_slack use lookup()
        # (not select_blocks), so only a measured kernel_bench calibration
        # ever registers this entry; the dict here just keeps the op known.
        return {"batch_cap": 8, "itl_slack_pct": 50}
    raise KeyError(op)


def select_blocks(op: str, shape: tuple, dtype) -> dict:
    """Block sizes for ``op`` on ``shape``/``dtype`` (cached per key).

    Resolution: in-memory cache -> persisted registrations (lazily loaded
    once per process) -> analytic default."""
    key = cache_key(op, shape, dtype)
    hit = _CACHE.get(key)
    if hit is None and not _persist_loaded:
        load_persistent()
        hit = _CACHE.get(key)
    if hit is None:
        hit = _CACHE[key] = _default_blocks(op, key.shape, key.dtype)
    return dict(hit)


def lookup(op: str, shape: tuple, dtype) -> Optional[dict]:
    """A registration for the key, or None — WITHOUT falling back to (or
    caching) the analytic default.  Lets resolver-tier callers tell a
    measured ``tune``/``register`` entry apart from the formula default
    (``select_blocks`` deliberately blurs that line for the hot path)."""
    key = cache_key(op, shape, dtype)
    hit = _CACHE.get(key)
    if hit is None and not _persist_loaded:
        load_persistent()
        hit = _CACHE.get(key)
    return dict(hit) if hit is not None else None


def _key_shape(op: str, args: tuple) -> tuple:
    """The cache-key shape for ``op`` given the kernel's positional args —
    MUST mirror how the ops.py wrappers build their select_blocks keys."""
    if op == "moe_gemm":                  # (x, w) -> (E, C, H, D)
        return tuple(args[0].shape) + (args[1].shape[-1],)
    if op == "grouped_gemm":              # (x, w, offsets) -> (N, H, D, E)
        return tuple(args[0].shape) + (args[1].shape[-1], args[1].shape[-3])
    if op in ("permute", "unpermute"):    # (x|buf, idx, ...) -> (N|T, h)
        return (args[1].shape[0], args[0].shape[-1])
    if op == "flash_decode":              # (q, k, v, lens) -> k.shape
        return tuple(args[1].shape)
    if op == "flash_chunk":               # (q, k, v, ...) -> q.shape + (S,)
        return tuple(args[0].shape) + (args[1].shape[1],)
    if op == "flash_chunk_paged":         # (q, kp, vp, bt, ...) -> q + (P, page)
        return tuple(args[0].shape) + (args[1].shape[0], args[1].shape[1])
    return tuple(args[0].shape)           # topk_gate: logits.shape


def tune(op: str, fn: Callable, candidates: list[dict], *args,
         shape: Optional[tuple] = None, dtype=None,
         warmup: int = 1, iters: int = 3) -> dict:
    """Time ``fn(*args, **blocks)`` per candidate, register + return the best.

    ``shape``/``dtype`` default to the key the ops.py wrapper for ``op``
    would build from the same arguments, so a tuned registration is
    guaranteed to be the one the serving path looks up.  Measured walltime
    only means something on the backend it ran on — and the winner IS
    persisted (via ``register``) for later processes on this machine, so
    don't ship a cache file tuned in interpret mode to a TPU host; set
    ``REPRO_AUTOTUNE_CACHE=""`` to keep a tuning run process-local.
    """
    import time as _time

    import jax

    if shape is None:
        shape = _key_shape(op, args)
    if dtype is None:
        dtype = args[0].dtype
    best, best_t, error = None, float("inf"), None
    for blocks in candidates:
        try:
            for _ in range(warmup):
                jax.block_until_ready(fn(*args, **blocks))
        except Exception as e:        # a block the compiler refuses
            error = e
            warnings.warn(f"autotune {op}{tuple(shape)}: candidate {blocks} "
                          f"failed: {type(e).__name__}: {e}")
            continue
        ts = []
        for _ in range(iters):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(*args, **blocks))
            ts.append(_time.perf_counter() - t0)
        t = sorted(ts)[len(ts) // 2]
        if t < best_t:
            best, best_t = blocks, t
    if best is None:
        raise RuntimeError(
            f"autotune {op}{tuple(shape)}: none of {len(candidates)} "
            f"candidates ran") from error
    register(op, shape, dtype, best)
    return dict(best)


__all__ = ["select_blocks", "lookup", "register", "tune", "cache_info",
           "clear_cache", "cache_key", "cache_path", "load_persistent",
           "CACHE_VERSION", "KernelKey", "VMEM_BUDGET_BYTES",
           "SCOPED_VMEM_BYTES", "grouped_gemm_vmem"]
