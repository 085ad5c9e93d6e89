"""Quantized paged KV-cache storage (int8 / fp8 pages + scale pools).

The serving decode path is bound twice by KV bytes: HBM *capacity* caps
concurrent slots and prefix-cache depth (page arithmetic — the currency
every scheduler mechanism spends), and HBM *bandwidth* bounds the
per-token attention gather.  Quantizing the page pools attacks both at
once — the PagedAttention + KV-quantization direction (vLLM; KIVI /
FP8-KV): an fp32 KV token row of ``head_dim`` floats becomes
``head_dim`` int8 (or fp8-e4m3) values plus ONE fp32 scale, a ~3.8x
byte reduction at head_dim 64 (2x vs bf16).

Storage contract
----------------
A quantized pool layer holds FOUR leaves instead of two::

    k_pages  [num_pages, page_size, kv_heads, head_dim]  int8 | fp8
    v_pages  [num_pages, page_size, kv_heads, head_dim]  int8 | fp8
    k_scale  [num_pages, page_size, kv_heads, 1]         float32
    v_scale  [num_pages, page_size, kv_heads, 1]         float32

The scale pools are a PARALLEL POOL indexed by the same page ids as the
payload pools — a scale row travels with its page through every host
mechanism (COW ``copy_page``, donation, ``truncate_slot``, handoff
``adopt_chain``) for free, because those mechanisms move page *ids*,
never bytes.  Scales are therefore part of the page's identity: a
prefix-cache hit shares payload and scales as one unit, and the byte
ledgers (``pool_bytes_per_device``, mem telemetry, health) count them
simply by summing leaves.  Keeping the scale leaves rank-4 (trailing
dim 1) matters: the pool axis family's single NamedSharding
(``P(pages, None, kv_heads, None)``) broadcasts over all four leaves,
so the scales shard their kv-head dim over ``model`` exactly like the
payload they describe.

A LATENT layer (multi-head latent attention) holds ONE float leaf,
``c_pages [num_pages, page_size, stored]`` (:func:`latent_pool_layer`):
the vector every query head reads as its key and, in its leading
features, as its value.  It is indexed by the same page ids and moves
with its page like any other leaf; it has no head dim and takes no
quantized dtype (refused by name).

Quantization granularity is per token-row per kv-head (one scale per
written KV vector).  Coarser per-page scales would need requantization
on every append — pages fill token by token — which compounds error;
per-row scales quantize each vector exactly once, at write time, and
never touch it again.

Numerics: symmetric absmax.  ``scale = max|x| / qmax`` (qmax 127 for
int8, 448 for fp8-e4m3), ``q = cast(x / scale)`` (round+clip for int8,
dtype cast for fp8), ``dequant = q * scale``.  All scale math in fp32.
"""

import jax
import jax.numpy as jnp

__all__ = ["KV_QUANT_DTYPES", "is_quantized_kv", "kv_dtype_name",
           "kv_storage_dtype", "kv_qmax", "quantize_kv_rows",
           "dequantize_kv_rows", "paged_pool_layer", "paged_write",
           "paged_gather", "kv_page_bytes", "fp8_supported",
           "LATENT_LEAF", "LANES", "latent_stored_dim", "latent_pool_layer",
           "latent_page_bytes", "page_leaf"]

# accepted quantized kv_dtype spellings (the float spellings live in
# inference.engine.DTYPES); "fp8" is e4m3 — the inference-standard
# format (e5m2's 2-bit mantissa is a gradients format)
KV_QUANT_DTYPES = ("int8", "fp8")

_QMAX = {"int8": 127.0, "fp8": 448.0}

# A LATENT layer's entry (multi-head latent attention) holds ONE leaf
# instead of two: a token's cache in the layer is one vector -- the
# normed latent and the rotated shared key side by side -- that every
# query head reads as its key, and whose leading ``value_dim`` features
# are its value.  ``c_pages [num_pages, page_size, stored]``: no head
# dim (there is one head, and a size-1 second-minor dim is what the
# TPU's tiled layout pads to a whole sublane tile), tokens on the
# sublanes, features on the lanes.
LATENT_LEAF = "c_pages"
# the minor dim of a TPU tile
LANES = 128


def fp8_supported():
    """True when this jax runtime ships float8_e4m3fn."""
    return hasattr(jnp, "float8_e4m3fn")


def is_quantized_kv(dtype):
    """True for the string names of quantized KV dtypes ("int8"/"fp8");
    jnp dtypes and float names are the classic float pool path."""
    return isinstance(dtype, str) and dtype in KV_QUANT_DTYPES


def kv_qmax(name):
    return _QMAX[name]


def kv_storage_dtype(name):
    """Storage dtype for a quantized KV pool, validating runtime
    support (fp8 needs a jax build with float8_e4m3fn)."""
    if name == "int8":
        return jnp.int8
    if name == "fp8":
        if not fp8_supported():
            raise ValueError(
                "kv_dtype='fp8' needs a jax runtime with "
                "float8_e4m3fn; this build has none — use 'int8'")
        return jnp.float8_e4m3fn
    raise ValueError(f"unknown quantized kv dtype {name!r}; "
                     f"expected one of {KV_QUANT_DTYPES}")


def page_leaf(layer):
    """The array of one pool layer dict whose leading dims are [pages,
    page_size]: the K pages, or a latent layer's one leaf; None where
    the entry holds no pages (per-slot state, a ring, counters)."""
    return layer.get("k_pages", layer.get(LATENT_LEAF))


def kv_dtype_name(layer):
    """Canonical kv-dtype name of one pool layer dict (the live truth —
    health() reports what is allocated, not what was configured)."""
    dt = page_leaf(layer).dtype
    if "k_scale" in layer:
        return "int8" if dt == jnp.int8 else "fp8"
    return jnp.dtype(dt).name


def quantize_kv_rows(x, name):
    """Per-row symmetric quantization of KV vectors: ``x [..., d]`` ->
    ``(q [..., d] storage-dtype, scale [..., 1] f32)``.  The trailing
    scale dim keeps the result rank-aligned with the rank-4 scale pool
    (one broadcastable multiply dequantizes)."""
    qmax = _QMAX[name]
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    y = xf / scale
    if name == "int8":
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    else:
        q = y.astype(kv_storage_dtype(name))
    return q, scale


def dequantize_kv_rows(q, scale, dtype):
    """``q [..., d] * scale [..., 1]`` -> ``[..., d]`` in ``dtype``."""
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)) \
        .astype(dtype)


def paged_pool_layer(num_pages, page_size, kv_heads, head_dim, dtype,
                     v_dim=None):
    """One layer's pool leaves: two float pools classically, four
    leaves (int8/fp8 payload + f32 scale pools) when ``dtype`` is a
    quantized kv-dtype name.  ``head_dim`` is the width of a key and,
    unless ``v_dim`` says otherwise, of a value (MiMo-V2's keys are 192
    wide beside values of 128).  A layer that caches ONE vector a token
    for all heads, read as key and value (multi-head latent attention),
    builds its entry with :func:`latent_pool_layer` instead: one leaf,
    no head dim."""
    v_dim = head_dim if v_dim is None else v_dim
    if is_quantized_kv(dtype):
        st = kv_storage_dtype(dtype)
        return {
            "k_pages": jnp.zeros((num_pages, page_size, kv_heads,
                                  head_dim), st),
            "v_pages": jnp.zeros((num_pages, page_size, kv_heads,
                                  v_dim), st),
            "k_scale": jnp.zeros((num_pages, page_size, kv_heads, 1),
                                 jnp.float32),
            "v_scale": jnp.zeros((num_pages, page_size, kv_heads, 1),
                                 jnp.float32),
        }
    return {
        "k_pages": jnp.zeros((num_pages, page_size, kv_heads, head_dim),
                             dtype),
        "v_pages": jnp.zeros((num_pages, page_size, kv_heads, v_dim),
                             dtype),
    }


def latent_stored_dim(width):
    """The width a latent vector takes in the page pool: ``width``
    rounded up to whole lane tiles where it is over one (576 -> 640, 11%
    more than published).  A pool whose minor dim is no multiple of 128
    lanes is not what the chip keeps row-major, and each paged kernel
    call would copy the layer's whole pool in and out (PERF.md section
    6, PR 42 c; PR 54 for this leaf).  Zeros in the padding add nothing
    to a score, and a value is read from the leading features only."""
    if width <= LANES:
        return width
    return -(-width // LANES) * LANES


def _refuse_quantized_latent(dtype):
    if is_quantized_kv(dtype):
        raise ValueError(
            f"kv_dtype={dtype!r} over a latent page pool is not built: "
            "a latent entry holds the normed latent and the rotated "
            "shared key in one row, and one scale a row over both is "
            "another design -- serve this model with a float kv_dtype")


def latent_pool_layer(num_pages, page_size, width, dtype):
    """One LATENT layer's pool: the one leaf ``c_pages [num_pages,
    page_size, latent_stored_dim(width)]``.  A quantized ``dtype`` is
    refused by name: one scale a row over latent and rope key together
    is another design (the two halves differ in range)."""
    _refuse_quantized_latent(dtype)
    return {LATENT_LEAF: jnp.zeros(
        (num_pages, page_size, latent_stored_dim(width)), dtype)}


def latent_page_bytes(num_layers, width, page_size, dtype):
    """Exact bytes one page costs across ``num_layers`` latent layers
    AS STORED (the padding of :func:`latent_stored_dim` included); the
    published cost is ``width`` a token a layer."""
    _refuse_quantized_latent(dtype)
    return int(num_layers) * int(page_size) * latent_stored_dim(width) \
        * jnp.dtype(dtype).itemsize


def _qname(storage_dtype):
    return "int8" if storage_dtype == jnp.int8 else "fp8"


def paged_write(layer, page_ids, offsets, k_new, v_new):
    """Write K/V rows through the page table, quantizing iff the layer
    carries scale pools.  ``page_ids``/``offsets`` have any index shape
    X; ``k_new``/``v_new`` are ``X + (kv_heads, head_dim)``.  Returns
    the updated pool-leaf dict (same key set as ``layer``'s pool
    leaves).  Out-of-range page ids drop the write (``mode="drop"``) —
    the masking contract every paged branch already relies on — and the
    scale write uses the SAME masked ids, so payload and scale stay
    atomic per row.  The float path is byte-identical to the
    pre-quantization code (zero-cost-when-off: the branch is a
    trace-time dict-key check).  A LATENT layer takes its one vector a
    token as ``k_new`` (``X + (stored,)``, already padded to the leaf's
    width) and ``v_new`` None."""
    if LATENT_LEAF in layer:
        assert v_new is None, "a latent entry holds one vector a token"
        c_pages = layer[LATENT_LEAF]
        return {LATENT_LEAF: c_pages.at[page_ids, offsets].set(
            k_new.astype(c_pages.dtype), mode="drop")}
    k_pages, v_pages = layer["k_pages"], layer["v_pages"]
    if "k_scale" not in layer:
        return {
            "k_pages": k_pages.at[page_ids, offsets].set(
                k_new.astype(k_pages.dtype), mode="drop"),
            "v_pages": v_pages.at[page_ids, offsets].set(
                v_new.astype(v_pages.dtype), mode="drop"),
        }
    name = _qname(k_pages.dtype)
    kq, ks = quantize_kv_rows(k_new, name)
    vq, vs = quantize_kv_rows(v_new, name)
    return {
        "k_pages": k_pages.at[page_ids, offsets].set(kq, mode="drop"),
        "v_pages": v_pages.at[page_ids, offsets].set(vq, mode="drop"),
        "k_scale": layer["k_scale"].at[page_ids, offsets].set(
            ks, mode="drop"),
        "v_scale": layer["v_scale"].at[page_ids, offsets].set(
            vs, mode="drop"),
    }


def paged_gather(pools, page_table, dtype, value_dim=None):
    """Gather per-slot contiguous K/V buffers through the page table,
    dequantizing when the pools are quantized: returns ``(k, v)`` of
    shape ``[slots, max_pages * page_size, kv_heads, head_dim]``.  Over
    a LATENT layer the one leaf is gathered once: ``k`` is the vector
    as stored (one head) and ``v`` its leading ``value_dim`` features.
    The
    float path returns the raw gathered pages (exactly the
    pre-quantization behavior); the quantized path gathers payload AND
    scale pools (the scales ride the same page ids) and dequantizes to
    ``dtype`` — the jnp reference/oracle path, where the transient
    dequantized buffer is the price of GSPMD-partitionable ops.  The
    fast path on any topology is the Pallas kernel in
    ``ops/attention/decode.py``: its quantized variants fetch each
    page's scale block through the same prefetched page-table index
    map and dequantize in VMEM (shard_mapped per-shard on a
    multi-device mesh), so only quantized bytes stream from HBM."""
    from deepspeed_tpu.ops.attention.decode import gather_pages
    if LATENT_LEAF in pools:
        k = gather_pages(pools[LATENT_LEAF][:, :, None], page_table)
        return k, k[..., :value_dim]
    k = gather_pages(pools["k_pages"], page_table)
    v = gather_pages(pools["v_pages"], page_table)
    if "k_scale" in pools:
        ks = gather_pages(pools["k_scale"], page_table)
        vs = gather_pages(pools["v_scale"], page_table)
        k = dequantize_kv_rows(k, ks, dtype)
        v = dequantize_kv_rows(v, vs, dtype)
    return k, v


def kv_page_bytes(num_layers, kv_heads, head_dim, page_size, dtype,
                  v_dim=None):
    """Exact bytes one KV page costs across ALL layers (K + V payload
    plus, for quantized dtypes, the f32 scale rows; ``v_dim`` where a
    value is not as wide as a key).  This is the
    page-arithmetic unit the capacity ledgers and the autotuner's
    feasibility pruning bill in; it must agree with the allocated
    leaves' ``nbytes`` to the byte (pinned by tests/unit/
    test_kv_quant.py against real device pools)."""
    width = head_dim + (head_dim if v_dim is None else v_dim)
    if is_quantized_kv(dtype):
        per_token = width * jnp.dtype(kv_storage_dtype(dtype)).itemsize \
            + 2 * 4                              # + one f32 scale each
    else:
        per_token = width * jnp.dtype(dtype).itemsize
    return int(num_layers) * int(page_size) * int(kv_heads) * per_token
