"""One KV-cache API: dense / SWA-ring / paged layouts behind the
``KVCache`` protocol (see repro.cache.base for the contract).

``make_cache`` is the single construction point the model layers use;
``layout`` semantics:

  * ``"ring"``  (default) — today's behavior: sliding-window layers get a
    window-sized ring buffer, everything else a dense cache.
  * ``"dense"`` — force dense everywhere (the slot scheduler's
    requirement: absolute slots).
  * ``"paged"`` — page-pool + block-table for every layer; a windowed
    layer's pages hold every position too, and its window is a mask (the
    slot scheduler's requirement, as for dense).

``bits`` picks the quantized storage width (8 = int8, 4 = packed int4
nibbles along the head dim — half the cache bytes); it is static aux
data on every layout, rides ``scales``/``state_dict``, and the fused
kernels fold the nibble unpack into their dequant epilogue.
"""
from repro.cache.base import (DenseCache, KernelView, KVCache, KV_LEVELS,
                              LAYOUT_REGISTRY, RingCache, dequantize_kv,
                              kv_levels, quantize_kv)
from repro.cache.paged import (PagedCache, PrefixEntry, PrefixStore,
                               copy_pages, set_table_row,
                               splice_dense_into_pages)
from repro.core.packing import pack_int4, unpack_int4

LAYOUTS = ("dense", "ring", "paged")
KV_BITS = (8, 4)


def make_cache(batch, max_len, n_kv, head_dim, *, dtype, quantized=False,
               layout="ring", window=None, page_size=64, extra_pages=0,
               bits=8):
    """Build the right ``KVCache`` for one attention layer (see module
    docstring for the layout and bit-width semantics)."""
    if layout not in LAYOUTS:
        raise ValueError(f"unknown cache layout {layout!r} (use one of "
                         f"{LAYOUTS})")
    if bits not in KV_BITS:
        raise ValueError(f"unknown kv cache bits {bits!r} (use one of "
                         f"{KV_BITS})")
    if window is not None and layout == "ring" and window < max_len:
        return RingCache.init(batch, window, n_kv, head_dim, dtype=dtype,
                              quantized=quantized, bits=bits)
    if layout == "paged":
        return PagedCache.init(batch, max_len, n_kv, head_dim, dtype=dtype,
                               quantized=quantized, page_size=page_size,
                               extra_pages=extra_pages, bits=bits)
    return DenseCache.init(batch, max_len, n_kv, head_dim, dtype=dtype,
                           quantized=quantized, bits=bits)


__all__ = [
    "KVCache", "KernelView", "DenseCache", "RingCache", "PagedCache",
    "PrefixStore", "PrefixEntry", "make_cache", "quantize_kv",
    "dequantize_kv", "copy_pages", "set_table_row",
    "splice_dense_into_pages", "KV_LEVELS", "kv_levels", "pack_int4",
    "unpack_int4", "LAYOUTS", "KV_BITS", "LAYOUT_REGISTRY",
]
