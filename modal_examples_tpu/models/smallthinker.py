"""SmallThinker (``smallthinker``, SmallThinker-21BA3B-Instruct): sliding-
window and global attention layers in one model, a router that reads the
layer's input before attention, many small ReGLU experts; served on the
engine's normal path.

Every layer is *attention + routed experts*; what differs is data
(``cfg.window_layout``, ``cfg.rope_layout``, one entry a layer):

- a **window** layer (``window_layout[l] == 1``) attends to itself and the
  ``sliding_window - 1`` positions before it;
- a **global** layer attends to its whole context;
- a layer with ``rope_layout[l] == 1`` rotates q and k (whole head width,
  half-split rotation), one with 0 is position-free. In the published model
  the two layouts coincide: window layers rotate, global layers do not.

Two page groups hold the K/V (docs/kv_cache.md, "Two page groups"): the
global layers' in ``PagedKVCache``'s first two leaves, a page for every
position of a context, under the engine's page table; the window layers' in
the cache's **window group** (``cfg.window_group``), where a sequence holds
at most ``ops.window_ring_pages(window, page_size)`` pages (the window's and one of slack) and
uses them as a ring: position ``p`` lives in page
``window_tables[b, (p // page_size) % ring]``, so a page the window has left
is written over by the one that enters it. The programs take the group's
leaves as ``state=(k, v)`` and its table as ``window_tables=``.

The layer, input ``x`` (the residual stream):
``r = W_r x`` in float32, **before** ``RMS_in``: the router reads the layer's
input; ``h = x + Attn(RMS_in(x))``; ``y = h + MoE(RMS_post(h); r)``: the
``top_k`` largest of ``r``, a softmax over those (Mixtral's route, as
``_experts`` says), then
``sum_e w_e W_down,e (relu(W_gate,e z) * W_up,e z)`` through
``moe.moe_swiglu_sparse`` with ``activation="relu"``, the experts' stacks
kept ``[L, E, ...]`` and indexed ``[layer, expert]`` where a tile multiplies.
No shared expert, no dense layer, no q/k norm, no bias; the head is its own
matrix.

**Prefill** (one form for the bucket call, a chunk and ``forward``): the
keys of a layer are the cached positions right before the call's first,
gathered at a static length (the prefix bucket for a global layer, at most
a window for a window layer), and the call's own; the flash kernel runs with
a static offset, the window as its k grid's start, and ``k_first`` masking
the gathered rows that lie before the sequence's start when the bucket is
longer than the offset. The chunk's offset is an argument of the program
(``chunk_offset_runtime``: one program a prefix bucket, GLM's form of
ROADMAP.md D12). A window layer writes only the rows of the last ``ring``
pages up to the row's end, so one call never writes a ring page twice.

**Decode**: a global layer attends over the engine's table, a window layer
over its ring from the oldest page the window reaches
(``ops.window_decode_span``), the first page's positions from before the
window masked by ``starts``; both groups' new rows are scattered once after
the layers. ``paged_impl_plan`` names what runs. On a TPU at the published
shapes (4 K/V heads of 128, pages of 16, bf16) both groups go through the
ragged kernel's all-heads ``flat`` form, which reads a slot's live pages in
place, a ring from its first page with a wrap: a page of ``[16, 4, 128]``
reaches it as ``(64, 128)`` rows by a reshape the compiler proves a bitcast
(``ops.paged_attention.flat_view_is_free``), the 28 query heads as 32 rows.
The kernel's ``grouped`` form is not used: it slices every token-major page
by head in VMEM and a group of 7 fills 7 of a product's 128 rows, 2.6-2.9x
slower than the loop (PERF.md section 6, PR 41). Elsewhere (the CPU, other
shapes, ``paged_impl="xla"``) both run the chunked XLA loop, a window layer
over its table rolled to start at that page (``ops.window_decode_view``). A
window layer's attention, the kernel or the loop's gathers and the window's
worth gathered before a chunk, runs under ``mtpu.window_attention``, not
under ``mtpu.page_gather``: the scope is the layer's whole attention.

The layers are scanned a *period* at a time (the shortest repeating unit of
the two layouts, four layers here), the period's layers unrolled in the
scan's body: one body whatever the depth.

The plain reference is ``models/smallthinker_reference.py``. What this model
does not do yet is refused by name where the engine is built
(``SmallThinkerConfig.unsupported``).
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

from ..ops import (
    is_quantized,
    kv_gather,
    paged_decode_attention_chunked,
    paged_window_decode_attention_chunked,
    paged_window_decode_attention_ragged,
    sharded_ragged_decode,
)
from ..ops import scopes as _scopes
from ..ops.flash_attention import flash_attention_chunked
from . import layers
from . import moe as _moe
from .layers import refuse
from .layers import scatter_rows as _scatter_rows
from .lfm2 import _rope, tile_rows


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    dim: int = 2560
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    #: one entry a layer: 1 = sliding-window attention / rotary embedding
    window_layout: tuple = (0, 1, 1, 1) * 13
    rope_layout: tuple = (0, 1, 1, 1) * 13
    sliding_window: int = 4096
    moe_ffn_dim: int = 768
    n_experts: int = 64
    top_k_experts: int = 6
    rope_theta: float = 1500000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 16384
    dtype: str = "bfloat16"

    #: features of the engine this model's programs do not implement yet:
    #: ``LLMEngine`` refuses each by name where it is asked for. The prefix
    #: cache: a hit would need the shared prefix's last window of positions
    #: in the window group's pages, which belong to one sequence
    unsupported = (
        "prefix caching", "int8 KV cache", "speculative decoding",
        "disaggregated transfer", "tensor parallelism",
        "LoRA", "vision", "a Pallas scatter_impl",
    )
    #: ``decode_step(return_counts=True)`` hands back [pairs, tile rows]
    counts_expert_tile_rows = True
    #: the chunk program takes its offset as an argument (engine._chunk_key)
    chunk_offset_runtime = True

    def __post_init__(self):
        if len(self.window_layout) != len(self.rope_layout):
            raise ValueError("window_layout and rope_layout name different depths")
        if not set(self.window_layout) | set(self.rope_layout) <= {0, 1}:
            raise ValueError("a layout's entries are 0 or 1")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("query heads must be whole groups of K/V heads")
        if self.sliding_window < 1:
            raise ValueError("sliding_window < 1")

    # -- the seam LLMEngine reads (docs/mla.md) ---------------------------------

    @property
    def model(self):
        """The module that holds this configuration's programs."""
        return sys.modules[__name__]

    @property
    def cache_leaf_shapes(self) -> tuple[tuple[int, int], tuple[int, int]]:
        """Per-token shape of a layer's K and of its V, in either page group."""
        return ((self.n_kv_heads, self.head_dim),) * 2

    @property
    def n_cache_layers(self) -> int:
        """Layers the first two paged leaves cover: the global ones (at
        least a row, so that the leaves exist for a model with none)."""
        return max(1, self.n_layers - self.n_window_layers)

    @property
    def window_group(self) -> tuple[int, int] | None:
        """``(layers, window)`` of the second page group: the window layers,
        each keeping the last ``sliding_window`` positions of a context."""
        n = self.n_window_layers
        return (n, self.sliding_window) if n else None

    @property
    def quant_targets(self) -> tuple[str, ...]:
        from .quantize import SMALLTHINKER_TARGETS

        return SMALLTHINKER_TARGETS

    # -- sizes -------------------------------------------------------------------

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def n_layers(self) -> int:
        return len(self.window_layout)

    @property
    def n_window_layers(self) -> int:
        return sum(self.window_layout)

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def period(self) -> int:
        """Layers of the shortest unit both layouts repeat: the scan's body."""
        kinds = tuple(zip(self.window_layout, self.rope_layout))
        L = len(kinds)
        return next(p for p in range(1, L + 1) if L % p == 0 and kinds == kinds[:p] * (L // p))

    @property
    def param_count(self) -> int:
        D, hd = self.dim, self.head_dim
        attn = D * (self.n_heads + 2 * self.n_kv_heads) * hd + self.n_heads * hd * D + D
        moe = self.n_experts * 3 * D * self.moe_ffn_dim + D * self.n_experts + D
        return 2 * self.vocab_size * D + D + self.n_layers * (attn + moe)

    @staticmethod
    def tiny(vocab_size: int = 512, **overrides) -> "SmallThinkerConfig":
        """Test-tier config: two periods of (global, window, window, window),
        a window of 32, 8 experts 2 a token, a group of 3 query heads."""
        base = dict(
            vocab_size=vocab_size, dim=64, n_heads=6, n_kv_heads=2, head_dim=16,
            window_layout=(0, 1, 1, 1) * 2, rope_layout=(0, 1, 1, 1) * 2,
            sliding_window=32, moe_ffn_dim=32, n_experts=8, top_k_experts=2,
            rope_theta=10000.0, max_seq_len=512,
        )
        base.update(overrides)
        return SmallThinkerConfig(**base)

    @staticmethod
    def from_hf_config(path: str | Path) -> "SmallThinkerConfig":
        """From a published ``config.json`` (``model_type`` ``smallthinker``).
        A file that runs the first layers of the published stack keeps the
        two layouts whole and says how many in ``num_hidden_layers``."""
        cfg = json.loads(Path(path).read_text())
        for key, want in (
            ("moe_primary_router_apply_softmax", True), ("norm_topk_prob", True),
            ("tie_word_embeddings", False), ("rope_scaling", None),
        ):
            if cfg.get(key, want) != want:
                raise NotImplementedError(
                    f"SmallThinkerConfig: {key}={cfg[key]!r} is not modelled (only {want!r})"
                )
        n = int(cfg["num_hidden_layers"])
        windows = tuple(cfg["sliding_window_layout"])
        ropes = tuple(cfg.get("rope_layout", windows))
        if min(len(windows), len(ropes)) < n:
            raise ValueError(f"the layouts name fewer than {n} layers")
        return SmallThinkerConfig(
            vocab_size=cfg["vocab_size"],
            dim=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            window_layout=windows[:n],
            rope_layout=ropes[:n],
            sliding_window=cfg["sliding_window_size"],
            moe_ffn_dim=cfg["moe_ffn_hidden_size"],
            n_experts=cfg["moe_num_primary_experts"],
            top_k_experts=cfg["moe_num_active_primary_experts"],
            rope_theta=float(cfg.get("rope_theta", 1500000.0)),
            norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_seq_len=cfg.get("max_position_embeddings", 16384),
        )


# -- parameters -------------------------------------------------------------

_ATTENTION_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo")
_MOE_LEAVES = ("mlp_norm", "router") + _moe.EXPERT_LEAVES


def init_params(key: jax.Array, cfg: SmallThinkerConfig) -> dict:
    """Random init; every layer's leaves stacked on axis 0."""
    dt = cfg.jnp_dtype
    D, hd, L = cfg.dim, cfg.head_dim, cfg.n_layers
    E, F = cfg.n_experts, cfg.moe_ffn_dim

    def dense(k, *shape):
        return layers.init_dense(k, shape, dtype=dt)

    k = jax.random.split(key, 10)
    return {
        "embed": layers.init_dense(k[0], (cfg.vocab_size, D), scale=D**-0.5, dtype=dt),
        "lm_head": dense(k[1], D, cfg.vocab_size),
        "final_norm": jnp.ones((D,), dt),
        "layers": {
            "attn_norm": jnp.ones((L, D), dt),
            "wq": dense(k[2], L, D, cfg.n_heads * hd),
            "wk": dense(k[3], L, D, cfg.n_kv_heads * hd),
            "wv": dense(k[4], L, D, cfg.n_kv_heads * hd),
            "wo": dense(k[5], L, cfg.n_heads * hd, D),
            "mlp_norm": jnp.ones((L, D), dt),
            "router": dense(k[6], L, D, E),
            "moe_gate": dense(k[7], L, E, D, F), "moe_up": dense(k[8], L, E, D, F),
            "moe_down": dense(k[9], L, E, F, D),
        },
    }


def partition_specs(cfg: SmallThinkerConfig) -> dict:
    refuse(cfg, "tensor parallelism")
    raise NotImplementedError("SmallThinkerConfig has no partition specs")


#: published tensor names under ``model.layers.N.``, as far as the catalog's
#: config and the ``smallthinker`` modelling code imply them: ours -> theirs
HF_LAYER_NAMES = {
    "attn_norm": "input_layernorm.weight",
    "mlp_norm": "post_attention_layernorm.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "router": "block_sparse_moe.primary_router.weight",
    "moe_gate": "block_sparse_moe.experts.{e}.gate.weight",
    "moe_up": "block_sparse_moe.experts.{e}.up.weight",
    "moe_down": "block_sparse_moe.experts.{e}.down.weight",
}


def load_hf_weights(model_dir, cfg: SmallThinkerConfig, *, quantization=None, dtype=None) -> dict:
    """The published checkpoint (``*.safetensors`` under ``model_dir``) as
    this module's tree: ``HF_LAYER_NAMES`` under ``model.layers.N.`` for the
    first ``cfg.n_layers`` layers, a torch ``Linear`` ``[out, in]``
    transposed, the experts stacked, the vocabulary's first ``vocab_size``
    rows of the embedding and of the head, the final norm ``model.norm``."""
    import numpy as np
    from safetensors import safe_open

    from .quantize import bits_of, quantize_weight_host

    files = sorted(Path(model_dir).glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no *.safetensors under {model_dir}")
    where = {}
    for f in files:
        with safe_open(str(f), framework="np") as st:
            where.update({name: f for name in st.keys()})

    def get(name):
        with safe_open(str(where[name]), framework="np") as st:
            return np.asarray(st.get_tensor(name), np.float32)

    dt = jnp.dtype(dtype or cfg.dtype)
    # the head with the layers' matrices, as ``quantize_llama`` takes a tree's
    targets = (*cfg.quant_targets, "lm_head") if quantization else ()

    def one(ours, prefix):
        theirs = HF_LAYER_NAMES[ours]
        if "{e}" in theirs:
            return np.stack([get(prefix + theirs.format(e=e)).T for e in range(cfg.n_experts)])
        a = get(prefix + theirs)
        return a.T if a.ndim == 2 else a

    def put(ours, full):
        if ours in targets:
            return quantize_weight_host(full, bits_of(quantization))
        return jnp.asarray(full, dt)

    return {
        "embed": jnp.asarray(get("model.embed_tokens.weight")[: cfg.vocab_size], dt),
        "lm_head": put("lm_head", get("lm_head.weight")[: cfg.vocab_size].T),
        "final_norm": jnp.asarray(get("model.norm.weight"), dt),
        "layers": {
            ours: put(ours, np.stack([
                one(ours, f"model.layers.{i}.") for i in range(cfg.n_layers)
            ]))
            for ours in _ATTENTION_LEAVES + _MOE_LEAVES
        },
    }


def paged_impl_plan(
    cfg: SmallThinkerConfig, page_size: int, impl: str | None = None,
    scatter_impl: str = "xla", *, kv_dtype="bfloat16", mesh=None, warn: bool = True,
    expert_dtype=None,
) -> dict:
    """What runs for this model, chosen from what can be seen here.
    Attention (``attention`` for the global layers, ``window_attention`` for
    the window layers, one choice for both): ``ragged`` / ``ragged-ring``,
    the ragged kernel's ``flat`` form over each group's pages in place, a
    window layer's ring from the first page its window reaches
    (``ops.window_decode_span``), where the backend is a TPU, the pages are
    whole tiles (``ragged_shapes_ok``), they reach the kernel as
    ``(page x heads, head_dim)`` rows without a copy of the cache
    (``flat_view_is_free``: 4 K/V heads, or a multiple of 8) and are two
    bytes an element; ``xla-gather`` / ``xla-gather-ring``, the chunked XLA
    loop, everywhere else. ``impl`` ``"xla"`` / ``"pallas"`` force one (the
    kernel runs in the interpreter off the chip); a forced kernel the shapes
    refuse on a TPU is named in ``downgraded``. The kernel's ``grouped``
    form is never chosen: at the published 4 K/V heads with a group of 7 it
    was 2.6-2.9x slower than the loop on the v5e (it slices every token-major
    page by head in VMEM; PERF.md section 6, PR 41), where ``flat`` is
    2.9-3.9x faster (PR 42). The scatter is XLA's; another is refused. The
    routed experts' tile loop in a decode step (``expert_scan``):
    ``moe.expert_scan_form``'s choice for experts of ``expert_dtype``
    (unset: the model's own)."""
    from ..ops.kv_quant import resolve_kv_dtype
    from ..ops.paged_attention import flat_view_is_free, ragged_shapes_ok

    if scatter_impl != "xla":
        refuse(cfg, "a Pallas scatter_impl")
    if mesh is not None:
        refuse(cfg, "tensor parallelism")
    kvd = resolve_kv_dtype(kv_dtype)
    if kvd == "int8":
        refuse(cfg, "int8 KV cache")
    on_tpu = jax.default_backend() == "tpu"
    shapes_ok = (
        ragged_shapes_ok(cfg.head_dim, page_size) and flat_view_is_free(cfg.n_kv_heads)
        and jnp.dtype(kvd).itemsize == 2
    )
    if impl is None:
        ragged = on_tpu and shapes_ok
    else:
        ragged = impl == "pallas" and (shapes_ok or not on_tpu)
    downgraded = []
    if impl == "pallas" and not ragged:
        downgraded.append(
            f"paged_impl=pallas -> xla-gather (n_kv_heads={cfg.n_kv_heads}, head_dim="
            f"{cfg.head_dim}, page_size={page_size}, {kvd} pages: the flat form wants 4 or "
            "8k heads of 128k, pages of 16k, two bytes an element)"
        )
        if warn:
            import warnings

            warnings.warn("requested Pallas impl downgraded: " + downgraded[0], stacklevel=2)
    ring = None
    if cfg.window_group:
        ring = "ragged-ring" if ragged else "xla-gather-ring"
    return {
        "attention": "ragged" if ragged else "xla-gather",
        "ragged_variant": "flat" if ragged else None, "scatter": "xla",
        "kv_dtype": str(kvd), "tp": 1, "downgraded": downgraded,
        "window_attention": ring,
        "expert_scan": _moe.expert_scan_form(
            1, cfg.dim, cfg.moe_ffn_dim, expert_dtype or cfg.dtype
        ),
    }


# -- the layer's parts ------------------------------------------------------------


def _layer(params, i):
    """Layer ``i`` (traced or not) of the stack: its own rows of the small
    leaves, the experts' whole stacks and its index into them
    (``moe.scan_layers`` says why)."""
    stack = params["layers"]
    small = jax.tree.map(
        lambda w: w[i], {k: v for k, v in stack.items() if k not in _moe.EXPERT_LEAVES}
    )
    return dict(small, **{k: stack[k] for k in _moe.EXPERT_LEAVES}, expert_layer=i)


def _router_logits(layer, x):
    """x [..., D], the layer's input as it enters (not normed) -> [T, E] f32."""
    with jax.named_scope(_scopes.ROUTER):
        return jnp.einsum(
            "td,de->te", x.reshape(-1, x.shape[-1]).astype(jnp.float32),
            layer["router"].astype(jnp.float32),
        )


def _qkv(layer, u, cos, sin, cfg, rotate: bool):
    """u [..., D] (normed) -> q [..., Hq, hd], k and v [..., Hkv, hd]; q and
    k rotated where the layer has positions."""
    dt, hd = u.dtype, cfg.head_dim
    q = layers.mm(u, layer["wq"]).astype(dt).reshape(*u.shape[:-1], cfg.n_heads, hd)
    k = layers.mm(u, layer["wk"]).astype(dt).reshape(*u.shape[:-1], cfg.n_kv_heads, hd)
    v = layers.mm(u, layer["wv"]).astype(dt).reshape(*u.shape[:-1], cfg.n_kv_heads, hd)
    if rotate:
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    return q, k, v


def _experts(layer, h, logits, cfg, token_mask):
    """``h + MoE(RMS_post(h); logits)`` and the layer's [pairs, tile rows].
    The route is Mixtral's (``moe.moe_swiglu_routed``'s default): the top-k
    of the softmax, renormalised, is "the top-k of the logits, a softmax
    over the k": the softmax keeps the order and its denominator cancels."""
    z = layers.rms_norm(h, layer["mlp_norm"], cfg.norm_eps).reshape(-1, cfg.dim)
    mask = None if token_mask is None else token_mask.reshape(-1)
    with jax.named_scope(_scopes.ROUTER):
        weights, ids = _moe.route_group_limited(
            jax.nn.softmax(logits, axis=-1), cfg.top_k_experts, renormalize=True
        )
    out, _ = _moe.moe_swiglu_sparse(
        *(layer[n] for n in _moe.EXPERT_LEAVES), z, ids, weights,
        token_mask=mask, layer=layer["expert_layer"], activation="relu",
    )
    counts = tile_rows(
        ids, mask, cfg.n_experts,
        _moe.expert_tile(z.shape[0], cfg.top_k_experts, cfg.n_experts),
    )
    return h + out.astype(h.dtype).reshape(h.shape), counts


def _logits(params, x, cfg):
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return layers.mm(x, params["lm_head"])


def _scan_periods(cfg, body, carry):
    """``body(carry, l, kind) -> (carry, y)`` over every layer ``l`` with its
    ``kind = (window?, rotate?)``: a ``lax.scan`` over the periods whose body
    unrolls one period. Returns (carry, ys), a layer's ``y`` (a pytree of
    arrays) stacked ``[L, ...]`` in layer order."""
    P = cfg.period
    kinds = tuple(zip(cfg.window_layout[:P], cfg.rope_layout[:P]))

    def period(carry, p):
        ys = []
        for j, kind in enumerate(kinds):
            carry, y = body(carry, p * P + j, kind)
            ys.append(y)
        return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)

    carry, ys = jax.lax.scan(period, carry, jnp.arange(cfg.n_layers // P))
    return carry, jax.tree.map(lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), ys)


def _group_rows(cfg):
    """Each layer's row in its page group, and the layers of each group, in
    layer order: (row [L] int32, global layers, window layers)."""
    seen, rows, groups = [0, 0], [], ([], [])
    for l, w in enumerate(cfg.window_layout):
        rows.append(seen[w])
        groups[w].append(l)
        seen[w] += 1
    return jnp.asarray(rows, jnp.int32), groups[0], groups[1]


def _check_serving(cfg, k_pages, state, window_tables, mesh, input_embeds=None):
    if mesh is not None:
        refuse(cfg, "tensor parallelism")
    if input_embeds is not None:
        refuse(cfg, "vision")
    if is_quantized(k_pages):
        refuse(cfg, "int8 KV cache")
    if cfg.window_group and (len(state) != 2 or window_tables is None):
        raise ValueError(
            "SmallThinkerConfig's programs take state=(the window group's K, its V) "
            "and window_tables="
        )


# -- forward, for tests and tools -----------------------------------------------


def forward(params: dict, tokens: jax.Array, cfg: SmallThinkerConfig, *,
            attn_impl: str = "flash", lora=None):
    """Full-sequence forward of the program's own layers, no cache: [B, S] ->
    logits [B, S, vocab]."""
    if lora is not None:
        refuse(cfg, "LoRA")
    B, S = tokens.shape
    logits, *_ = _prefill_impl(
        params, tokens, None, None, (), None, None, jnp.full((B,), S, jnp.int32), cfg,
        q_offset=0, prefix_len=0, all_logits=True,
    )
    return logits


# -- serving: prefill + paged decode ----------------------------------------


def _prefill_impl(params, tokens, k_pages, v_pages, state, page_tables, window_tables,
                  lens, cfg, *, q_offset, prefix_len: int, all_logits: bool = False):
    """``lens`` valid tokens of [B, C] at global positions from ``q_offset``
    on (a traced scalar, or a static one) over the cached positions before
    it, gathered at static lengths: ``prefix_len >= q_offset`` in a global
    layer, at most a window in a window layer. Writes the call's own K/V in
    both groups. Without pages (``forward``): no prefix, nothing written."""
    B, C = tokens.shape
    P = prefix_len
    if not P:
        q_offset = 0
    W = cfg.sliding_window
    cached = k_pages is not None
    valid = jnp.arange(C)[None, :] < lens[:, None]
    positions = q_offset + jnp.broadcast_to(jnp.arange(C), (B, C))
    cos, sin = layers.rotary_embedding(positions, cfg.head_dim, cfg.rope_theta)
    rows, global_layers, window_layers = _group_rows(cfg)
    if cached:
        ps = k_pages.shape[2]
        if window_layers:
            wk_pages, wv_pages = state
            ring = window_tables.shape[1]
        # the gathered prefix ends right before the call's first position,
        # whatever the offset: column j holds position q_offset - P + j, and
        # the rows before the sequence's start (j < P - q_offset) are masked
        # for every query (k_first); a window layer gathers a window's worth
        P_w = min(P, -(-(W - 1) // ps) * ps)

        def prefix_pages(n_positions, table, wrap):
            page = (q_offset - n_positions) // ps + jnp.arange(n_positions // ps)
            page = jnp.maximum(page, 0)
            cols = page % wrap if wrap else page
            return jnp.take(table, cols, axis=1)  # [B, n_positions / ps]

        def heads_first(got):  # gathered pages [B, n, ps, Hkv, hd] -> [B, Hkv, n * ps, hd]
            return got.reshape(B, -1, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)

    def layer_fn(x, l, kind):
        window, rotate = kind
        layer = _layer(params, l)
        logits = _router_logits(layer, x)
        u = layers.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(layer, u, cos, sin, cfg, rotate)
        q, k_own, v_own = (a.transpose(0, 2, 1, 3) for a in (q, k, v))  # [B, heads, C, hd]
        n_pre = (P_w if window else P) if cached else 0
        k_all, v_all, k_first = k_own, v_own, None
        if n_pre:
            if window:
                with jax.named_scope(_scopes.WINDOW_ATTENTION):
                    tables = prefix_pages(n_pre, window_tables, ring)
                    pk, pv = (
                        heads_first(pages[rows[l], tables]) for pages in (wk_pages, wv_pages)
                    )
            else:
                with jax.named_scope(_scopes.PAGE_GATHER):
                    tables = prefix_pages(n_pre, page_tables, 0)
                    pk, pv = (
                        heads_first(kv_gather(pages, tables, layer=rows[l], dtype=cfg.jnp_dtype))
                        for pages in (k_pages, v_pages)
                    )
            k_all = jnp.concatenate([pk, k_own], axis=2)
            v_all = jnp.concatenate([pv, v_own], axis=2)
            k_first = jnp.maximum(n_pre - q_offset, 0)
        with jax.named_scope(_scopes.WINDOW_ATTENTION if window else _scopes.ATTENTION):
            o = flash_attention_chunked(
                q, k_all, v_all, q_offset=n_pre, sm_scale=cfg.softmax_scale,
                window=W if window else None, k_first=k_first,
            )
        o = o.transpose(0, 2, 1, 3).reshape(B, C, cfg.n_heads * cfg.head_dim)
        h = x + layers.mm(o, layer["wo"]).astype(x.dtype)
        y, _ = _experts(layer, h, logits, cfg, valid)
        return y, (k, v)  # [B, C, Hkv, hd] each

    x, (ks, vs) = _scan_periods(cfg, layer_fn, params["embed"][tokens])
    if cached:
        page = positions // ps
        slot = jnp.where(valid, positions % ps, 0)
        if global_layers:
            idx = jnp.where(valid, jnp.take_along_axis(page_tables, page, axis=1), 0)
            at = jnp.asarray(global_layers)
            k_pages = _scatter_rows(k_pages, ks[at], idx, slot)
            v_pages = _scatter_rows(v_pages, vs[at], idx, slot)
        if window_layers:
            # only the last ``ring`` pages up to the row's end are kept: a
            # longer call would write a ring page twice
            end_page = (q_offset + lens - 1) // ps
            keep = valid & (page > end_page[:, None] - ring)
            idx = jnp.where(keep, jnp.take_along_axis(window_tables, page % ring, axis=1), 0)
            w_slot = jnp.where(keep, slot, 0)
            at = jnp.asarray(window_layers)
            state = (
                _scatter_rows(wk_pages, ks[at], idx, w_slot),
                _scatter_rows(wv_pages, vs[at], idx, w_slot),
            )
    if all_logits:
        return _logits(params, x, cfg), k_pages, v_pages, state
    last = jnp.maximum(lens - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None].repeat(x.shape[-1], -1), 1)[:, 0]
    return _logits(params, x_last, cfg), k_pages, v_pages, state


def prefill(
    params: dict,
    tokens: jax.Array,  # [B, S] padded
    k_pages: jax.Array,  # [global layers, n_pages, page_size, Hkv, hd]
    v_pages: jax.Array,
    page_tables: jax.Array,  # [B, pages_per_seq]
    seq_lens: jax.Array,  # [B] true lengths
    cfg: SmallThinkerConfig,
    attn_impl: str = "flash",
    input_embeds=None,
    mesh=None,
    *,
    state: tuple = (),  # the window group's (K, V) [window layers, n_window_pages, ...]
    slot_ids=None,  # the seam's; nothing here is kept per slot
    window_tables=None,  # [B, ring]: the rows' window pages
):
    """Process prompts from their first token, filling both page groups.
    Returns (logits_last, k_pages, v_pages, state)."""
    _check_serving(cfg, k_pages, state, window_tables, mesh, input_embeds)
    return _prefill_impl(
        params, tokens, k_pages, v_pages, state, page_tables, window_tables, seq_lens,
        cfg, q_offset=0, prefix_len=0,
    )


def prefill_chunk(
    params: dict,
    tokens: jax.Array,  # [B, C] — one chunk of the prompt
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_tables: jax.Array,
    chunk_lens: jax.Array,  # [B] valid tokens in THIS chunk
    cfg: SmallThinkerConfig,
    *,
    q_offset,  # global position of the chunk's first token: int32 scalar, traced or not
    prefix_len: int | None = None,  # static: cached positions a global layer gathers (>= q_offset)
    attn_impl: str = "flash",
    mesh=None,
    state: tuple = (),
    slot_ids=None,
    window_tables=None,
):
    """One chunk of a long prompt at a run-time offset: a global layer
    attends to ``prefix_len`` gathered positions (those below the offset)
    and the chunk, a window layer to the window's worth before the chunk and
    the chunk; positions the window has left are neither read nor kept."""
    _check_serving(cfg, k_pages, state, window_tables, mesh)
    return _prefill_impl(
        params, tokens, k_pages, v_pages, state, page_tables, window_tables, chunk_lens,
        cfg, q_offset=q_offset, prefix_len=q_offset if prefix_len is None else prefix_len,
    )


def decode_step(
    params: dict,
    tokens: jax.Array,  # [B] int32 — current token per slot
    positions: jax.Array,  # [B] int32 — its position
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_tables: jax.Array,  # [B, pages_per_seq]
    active: jax.Array,  # [B] bool — live slots
    cfg: SmallThinkerConfig,
    impl: str | None = None,
    scatter_impl: str = "xla",
    ragged_variant: str | None = None,
    mesh=None,
    return_counts: bool = False,
    *,
    state: tuple = (),
    window_tables=None,  # [B, ring]: row b is slot b
):
    """One token of batched decode: every layer against its page group
    (read-only inside the step), then one scatter a group. A slot that is
    not ``active`` writes to the trash pages and routes no pair. Returns
    (logits [B, vocab], k_pages, v_pages, state) and, with
    ``return_counts``, [pairs, tile rows] of the routed layers."""
    _check_serving(cfg, k_pages, state, window_tables, mesh)
    plan = paged_impl_plan(cfg, k_pages.shape[2], impl, scatter_impl, kv_dtype=k_pages.dtype)
    ragged = plan["attention"] == "ragged"
    ps = k_pages.shape[2]
    B = tokens.shape[0]
    W = cfg.sliding_window
    live_pos = jnp.where(active, positions, 0).astype(jnp.int32)
    cos, sin = layers.rotary_embedding(positions, cfg.head_dim, cfg.rope_theta)  # [B, hd / 2]
    rows, global_layers, window_layers = _group_rows(cfg)
    if window_layers:
        wk_pages, wv_pages = state

    def layer_fn(carry, l, kind):
        x, counts = carry
        window, rotate = kind
        layer = _layer(params, l)
        logits = _router_logits(layer, x)
        u = layers.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(layer, u, cos, sin, cfg, rotate)  # [B, heads, hd]
        # the kernel's form is always the all-heads one (paged_impl_plan says why)
        if window and ragged:
            o = paged_window_decode_attention_ragged(
                q, wk_pages, wv_pages, rows[l], window_tables, live_pos, k, v,
                window=W, sm_scale=cfg.softmax_scale, variant="flat",
            )
        elif window:
            o = paged_window_decode_attention_chunked(
                q, wk_pages, wv_pages, rows[l], window_tables, live_pos, k, v,
                window=W, sm_scale=cfg.softmax_scale,
            )
        elif ragged:
            o = sharded_ragged_decode(
                None, q, k_pages, v_pages, rows[l], page_tables, live_pos, k, v,
                sm_scale=cfg.softmax_scale, variant="flat",
            )
        else:
            o = paged_decode_attention_chunked(
                q, k_pages, v_pages, rows[l], page_tables, live_pos, k, v,
                sm_scale=cfg.softmax_scale,
            )
        h = x + layers.mm(o.reshape(B, -1), layer["wo"]).astype(x.dtype)
        y, c = _experts(layer, h, logits, cfg, active)
        return (y, counts + c), (k, v)

    (x, counts), (ks, vs) = _scan_periods(
        cfg, layer_fn, (params["embed"][tokens], jnp.zeros((2,), jnp.int32))
    )
    page = live_pos // ps
    slot = jnp.where(active, positions % ps, 0)
    if global_layers:
        idx = jnp.where(active, jnp.take_along_axis(page_tables, page[:, None], axis=1)[:, 0], 0)
        at = jnp.asarray(global_layers)
        k_pages = _scatter_rows(k_pages, ks[at], idx, slot)
        v_pages = _scatter_rows(v_pages, vs[at], idx, slot)
    if window_layers:
        col = (page % window_tables.shape[1])[:, None]
        idx = jnp.where(active, jnp.take_along_axis(window_tables, col, axis=1)[:, 0], 0)
        at = jnp.asarray(window_layers)
        state = (
            _scatter_rows(wk_pages, ks[at], idx, slot),
            _scatter_rows(wv_pages, vs[at], idx, slot),
        )
    out = (_logits(params, x, cfg), k_pages, v_pages, state)
    return (*out, counts) if return_counts else out
