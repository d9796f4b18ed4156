"""Model assembly: init / train-forward / decode-step for every family.

Layer parameters are stacked on a leading axis and iterated with
``jax.lax.scan`` so the lowered HLO is layer-count-independent (critical for
the 40-cell x 512-device dry-run compile budget). Decode threads per-layer
caches through the same scan.

Families:
  dense           pre-norm GQA attention + SwiGLU
  moe             pre-norm attention (GQA or MLA) + routed MoE
  ssm             Mamba-2 blocks only
  hybrid          Zamba2-style: groups of mamba layers + one *shared*
                  attention/MLP block applied between groups
  audio           bidirectional encoder (frame embeddings in, CTC-ish head)
  vlm             patch-embedding prefix + causal LM backbone
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import perf_flags
from repro.models.config import ModelConfig
from repro.models.layers import (init_embedding, init_linear, rms_norm,
                                 swiglu)

Params = dict[str, Any]


# ============================================================ init helpers
def _init_dense_layer(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 5)
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    layer: Params = {
        "ln1": jnp.ones((d,), dtype),
        "ln2": jnp.ones((d,), dtype),
    }
    if cfg.mla is not None:
        layer["mla"] = mla_mod.init_mla(ks[0], d, cfg.n_heads, cfg.mla,
                                        dtype)._asdict()
    else:
        layer["attn"] = attn_mod.init_attn(
            ks[0], d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.qk_norm, dtype)._asdict()
        if not cfg.qk_norm:
            # keep pytree structure uniform for scan stacking
            layer["attn"]["q_norm"] = jnp.zeros((0,), dtype)
            layer["attn"]["k_norm"] = jnp.zeros((0,), dtype)
    if cfg.moe is not None:
        mp = moe_mod.init_moe(ks[1], d, cfg.moe, dtype)._asdict()
        if cfg.moe.num_shared == 0:
            mp["shared_gate"] = jnp.zeros((0,), dtype)
            mp["shared_up"] = jnp.zeros((0,), dtype)
            mp["shared_down"] = jnp.zeros((0,), dtype)
        layer["moe"] = mp
    else:
        layer["mlp"] = {
            "gate": init_linear(ks[2], d, cfg.d_ff, dtype),
            "up": init_linear(ks[3], d, cfg.d_ff, dtype),
            "down": init_linear(ks[4], cfg.d_ff, d, dtype),
        }
    return layer


def _init_ssm_layer(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    return {
        "ln": jnp.ones((cfg.d_model,), dtype),
        "ssm": ssm_mod.init_ssm(key, cfg.d_model, cfg.ssm, dtype)._asdict(),
    }


def _stack(trees: list[Params]) -> Params:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(cfg: ModelConfig, key) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 8)
    params: Params = {
        "embed": init_embedding(keys[0], cfg.vocab, cfg.d_model, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(keys[1], cfg.d_model, cfg.vocab,
                                        dtype)

    if cfg.family == "ssm":
        params["layers"] = _stack([
            _init_ssm_layer(keys[2 + i], cfg) for i in range(cfg.n_layers)])
    elif cfg.family == "hybrid":
        hb = cfg.hybrid
        n_mamba = hb.n_groups * hb.mamba_per_group
        mamba = [_init_ssm_layer(keys[2 + i], cfg) for i in range(n_mamba)]
        grouped = [
            _stack(mamba[g * hb.mamba_per_group:(g + 1) * hb.mamba_per_group])
            for g in range(hb.n_groups)]
        params["mamba_groups"] = _stack(grouped)
        params["tail_mamba"] = _stack([
            _init_ssm_layer(keys[2 + n_mamba + i], cfg)
            for i in range(hb.tail_mamba)])
        shared_cfg = dataclasses.replace(cfg, moe=None, mla=None)
        params["shared_attn"] = _init_dense_layer(keys[2 + cfg.n_layers],
                                                  shared_cfg)
    else:
        params["layers"] = _stack([
            _init_dense_layer(keys[2 + i], cfg) for i in range(cfg.n_layers)])

    if cfg.family in ("vlm", "audio"):
        params["frontend"] = init_linear(keys[-1], cfg.frontend_dim,
                                         cfg.d_model, dtype)
    return params


# ============================================================ block applies
def _attn_params(layer: Params) -> attn_mod.AttnParams:
    a = layer["attn"]
    qn = a["q_norm"] if a["q_norm"].size else None
    kn = a["k_norm"] if a["k_norm"].size else None
    return attn_mod.AttnParams(a["wq"], a["wk"], a["wv"], a["wo"], qn, kn)


def _moe_params(layer: Params) -> moe_mod.MoEParams:
    m = layer["moe"]
    return moe_mod.MoEParams(
        m["router"], m["w_gate"], m["w_up"], m["w_down"],
        m["shared_gate"] if m["shared_gate"].size else None,
        m["shared_up"] if m["shared_up"].size else None,
        m["shared_down"] if m["shared_down"].size else None)


def _sp_pin(h: jax.Array) -> jax.Array:
    """§Perf `sp_pin`: keep intra-block activations sequence-sharded so TP
    reductions move S-sharded tensors instead of full activations."""
    if not perf_flags.enabled("sp_pin") or h.ndim != 3:
        return h
    from jax.sharding import PartitionSpec as P
    try:
        mesh = jax.sharding.get_abstract_mesh()
        dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        return jax.lax.with_sharding_constraint(
            h, P(dp or None, "model", None))
    except Exception:
        return h


def _dense_block(cfg: ModelConfig, layer: Params, x: jax.Array,
                 use_kernel: bool) -> tuple[jax.Array, jax.Array]:
    """Pre-norm attention + FFN/MoE. Returns (x, aux_loss)."""
    h = _sp_pin(rms_norm(x, layer["ln1"], cfg.rms_eps))
    if cfg.mla is not None:
        a = layer["mla"]
        attn_out = mla_mod.mla_train(
            mla_mod.MLAParams(**a), h, cfg.mla, n_heads=cfg.n_heads,
            rope_theta=cfg.rope_theta, rms_eps=cfg.rms_eps,
            causal=cfg.causal)
    else:
        attn_out = attn_mod.attention_train(
            _attn_params(layer), h, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, d_head=cfg.head_dim, causal=cfg.causal,
            rope_theta=cfg.rope_theta, rms_eps=cfg.rms_eps,
            use_kernel=use_kernel)
    x = x + attn_out
    h = _sp_pin(rms_norm(x, layer["ln2"], cfg.rms_eps))
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        ffn_out, aux = moe_mod.moe_forward(_moe_params(layer), h, cfg.moe)
    else:
        m = layer["mlp"]
        ffn_out = swiglu(h, m["gate"], m["up"], m["down"])
    return x + _sp_pin(ffn_out), aux


def _ssm_block(cfg: ModelConfig, layer: Params, x: jax.Array,
               use_kernel: bool) -> jax.Array:
    h = rms_norm(x, layer["ln"], cfg.rms_eps)
    return x + ssm_mod.ssm_forward(
        ssm_mod.SSMParams(**layer["ssm"]), h, cfg.ssm,
        rms_eps=cfg.rms_eps, use_kernel=use_kernel)


# ============================================================ train forward
def forward(cfg: ModelConfig, params: Params, batch: dict[str, jax.Array], *,
            use_kernel: bool = False, remat: bool = False,
            activation_spec=None) -> tuple[jax.Array, jax.Array]:
    """Returns (logits (B, S, V), aux_loss scalar).

    ``activation_spec``: optional PartitionSpec pinned onto the residual
    stream between layers (Megatron-style sequence parallelism — shards the
    scan carry that dominates checkpointed-activation memory at 4k+ seq)."""
    def _pin(h):
        if activation_spec is None:
            return h
        return jax.lax.with_sharding_constraint(h, activation_spec)
    if cfg.family == "audio":
        x = jnp.einsum("bsf,fd->bsd", batch["frames"], params["frontend"])
    else:
        x = params["embed"][batch["tokens"]]
        if cfg.family == "vlm":
            patches = jnp.einsum("bpf,fd->bpd", batch["patches"],
                                 params["frontend"])
            x = jnp.concatenate([patches, x], axis=1)

    if remat and perf_flags.enabled("remat_dots"):
        _ckpt = lambda f: jax.checkpoint(
            f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    else:
        _ckpt = jax.checkpoint
    x = _pin(x)
    if cfg.family == "ssm":
        def body(carry, layer):
            return _pin(_ssm_block(cfg, layer, carry, use_kernel)), None
        if remat:
            body = _ckpt(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
        aux = jnp.zeros((), jnp.float32)
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def mamba_body(carry, layer):
            return _pin(_ssm_block(cfg, layer, carry, use_kernel)), None

        def group_body(carry, group_layers):
            h, _ = jax.lax.scan(mamba_body, carry, group_layers)
            h, _ = _dense_block(cfg, shared, h, use_kernel)
            return _pin(h), None
        if remat:
            group_body = _ckpt(group_body)
        x, _ = jax.lax.scan(group_body, x, params["mamba_groups"])
        x, _ = jax.lax.scan(mamba_body, x, params["tail_mamba"])
        aux = jnp.zeros((), jnp.float32)
    else:
        def body(carry, layer):
            h, aux = _dense_block(cfg, layer, carry, use_kernel)
            return _pin(h), aux
        if remat:
            body = _ckpt(body)
        x, auxes = jax.lax.scan(body, x, params["layers"])
        aux = jnp.sum(auxes)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    if cfg.family == "vlm":  # strip the image-prefix positions
        logits = logits[:, cfg.num_patches:]
    return logits, aux


def loss_fn(cfg: ModelConfig, params: Params, batch: dict[str, jax.Array],
            *, use_kernel: bool = False, remat: bool = False,
            activation_spec=None) -> jax.Array:
    logits, aux = forward(cfg, params, batch, use_kernel=use_kernel,
                          remat=remat, activation_spec=activation_spec)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = jnp.where(labels >= 0, nll, 0.0)
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0) + aux


# ============================================================ prefill
def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            max_len: int, *, patches: jax.Array | None = None,
            true_len: jax.Array | None = None, attn_kernel: bool = False
            ) -> tuple[jax.Array, "DecodeCache"]:
    """Batched prompt processing (the paper's NPU prefill phase, §4.3):
    one parallel pass that returns next-token logits AND a filled decode
    cache (KV / latent / SSM state), padded to ``max_len``.

    tokens: (B, S) right-aligned prompts, all the same length (the serving
    engine buckets; ragged support lives there via per-seq lengths).

    ``true_len``: optional dynamic prompt length (scalar or (B,)) when
    ``tokens`` is right-PADDED to a compile-time bucket (pow-2 padding caps
    the jit-cache to O(log max_len) entries). Causality guarantees the
    first ``true_len`` positions are unaffected by padding; the returned
    logits are taken at position ``true_len - 1`` and cache lengths are set
    to ``true_len``, so stale padded K/V past it is dead and overwritten by
    subsequent decode appends. Only valid for positional-cache families
    (attention); SSM/hybrid running state would absorb the padding.

    ``attn_kernel`` (static) runs the GQA attention layers of the
    attention families through the Pallas flash kernel
    (``attention_prefill(use_kernel=True)``), which skips the dead
    positions' work; MLA and the hybrid's shared attention keep their jnp
    paths."""
    B, S = tokens.shape
    with jax.named_scope("model.embed"):
        x = params["embed"][tokens]
    n_prefix = 0
    if cfg.family == "vlm" and patches is not None:
        px = jnp.einsum("bpf,fd->bpd", patches, params["frontend"])
        x = jnp.concatenate([px, x], axis=1)
        n_prefix = patches.shape[1]
    Sfull = S + n_prefix
    pad = max_len - Sfull
    assert pad >= 0, (max_len, Sfull)
    if true_len is not None and cfg.family in ("ssm", "hybrid"):
        raise ValueError("bucketed prefill (true_len) requires a "
                         "positional cache; SSM state absorbs padding")
    cache = init_decode_cache(cfg, B, max_len)
    if true_len is None:
        lens = jnp.full((B,), Sfull, jnp.int32)
    else:
        lens = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32),
                                (B,)) + n_prefix

    def pad_seq(arr, axis):
        widths = [(0, 0)] * arr.ndim
        widths[axis] = (0, pad)
        return jnp.pad(arr, widths)

    if cfg.family in ("dense", "vlm") or (cfg.family == "moe"
                                          and cfg.mla is None):
        def body(carry, layer):
            h = carry
            with jax.named_scope("model.prefill_attention"):
                hn = rms_norm(h, layer["ln1"], cfg.rms_eps)
                attn_out, k, v = attn_mod.attention_prefill(
                    _attn_params(layer), hn, n_heads=cfg.n_heads,
                    n_kv=cfg.n_kv_heads, d_head=cfg.head_dim,
                    causal=cfg.causal, rope_theta=cfg.rope_theta,
                    rms_eps=cfg.rms_eps,
                    true_len=None if true_len is None else lens,
                    use_kernel=attn_kernel)
                h = h + attn_out
            with jax.named_scope("model.prefill_mlp"):
                hn = rms_norm(h, layer["ln2"], cfg.rms_eps)
                if cfg.moe is not None:
                    ffn, _ = moe_mod.moe_forward(_moe_params(layer), hn,
                                                 cfg.moe)
                else:
                    m = layer["mlp"]
                    ffn = swiglu(hn, m["gate"], m["up"], m["down"])
                return h + ffn, (k, v)

        with jax.named_scope("model.layers"):
            x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
        with jax.named_scope("kv.prefill_cache"):
            cache = cache._replace(k=pad_seq(ks, 3), v=pad_seq(vs, 3))

    elif cfg.family == "moe":                      # MLA
        def body(carry, layer):
            h = carry
            hn = rms_norm(h, layer["ln1"], cfg.rms_eps)
            attn_out, ckv, krp = mla_mod.mla_prefill(
                mla_mod.MLAParams(**layer["mla"]), hn, cfg.mla,
                n_heads=cfg.n_heads, rope_theta=cfg.rope_theta,
                rms_eps=cfg.rms_eps, causal=cfg.causal)
            h = h + attn_out
            hn = rms_norm(h, layer["ln2"], cfg.rms_eps)
            ffn, _ = moe_mod.moe_forward(_moe_params(layer), hn, cfg.moe)
            return h + ffn, (ckv, krp)

        x, (ckvs, krps) = jax.lax.scan(body, x, params["layers"])
        cache = cache._replace(ckv=pad_seq(ckvs, 2), krope=pad_seq(krps, 2))

    elif cfg.family == "ssm":
        def body(carry, layer):
            h = carry
            hn = rms_norm(h, layer["ln"], cfg.rms_eps)
            out, c = ssm_mod.ssm_prefill(
                ssm_mod.SSMParams(**layer["ssm"]), hn, cfg.ssm,
                rms_eps=cfg.rms_eps)
            return h + out, (c.conv, c.state)

        x, (convs, states) = jax.lax.scan(body, x, params["layers"])
        cache = cache._replace(conv=convs, state=states)

    elif cfg.family == "hybrid":
        hb = cfg.hybrid
        shared = params["shared_attn"]

        def mamba_body(carry, layer):
            h = carry
            hn = rms_norm(h, layer["ln"], cfg.rms_eps)
            out, c = ssm_mod.ssm_prefill(
                ssm_mod.SSMParams(**layer["ssm"]), hn, cfg.ssm,
                rms_eps=cfg.rms_eps)
            return h + out, (c.conv, c.state)

        def group_body(carry, layers):
            h, caches = jax.lax.scan(mamba_body, carry, layers)
            hn = rms_norm(h, shared["ln1"], cfg.rms_eps)
            attn_out, k, v = attn_mod.attention_prefill(
                _attn_params(shared), hn, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, d_head=cfg.head_dim, causal=True,
                rope_theta=cfg.rope_theta, rms_eps=cfg.rms_eps)
            h = h + attn_out
            hn = rms_norm(h, shared["ln2"], cfg.rms_eps)
            m = shared["mlp"]
            return h + swiglu(hn, m["gate"], m["up"], m["down"]), \
                (caches, k, v)

        x, (gcaches, ks, vs) = jax.lax.scan(group_body, x,
                                            params["mamba_groups"])
        x, tcaches = jax.lax.scan(mamba_body, x, params["tail_mamba"])
        conv = jnp.concatenate(
            [gcaches[0].reshape((-1,) + gcaches[0].shape[2:]), tcaches[0]])
        state = jnp.concatenate(
            [gcaches[1].reshape((-1,) + gcaches[1].shape[2:]), tcaches[1]])
        cache = cache._replace(conv=conv, state=state,
                               k=pad_seq(ks, 3), v=pad_seq(vs, 3))
    else:
        raise ValueError(f"{cfg.name}: prefill unsupported for family "
                         f"{cfg.family}")

    with jax.named_scope("model.head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        if true_len is None:
            last = x[:, -1]
        else:   # last REAL token of each (possibly bucket-padded) prompt
            last = jnp.take_along_axis(x, (lens - 1)[:, None, None],
                                       axis=1)[:, 0]
        logits = jnp.einsum("bd,dv->bv", last, head)
    return logits, cache._replace(lengths=lens)


def prefill_suffix(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   prefix_k: jax.Array, prefix_v: jax.Array,
                   prefix_len: jax.Array, *,
                   true_len: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Suffix-only prefill for prefix-cache admissions (PR 7).

    Processes only the NOVEL tail of a prompt whose first ``prefix_len``
    tokens already have cache-resident K/V (gathered from the paged pool
    through the sharer's block table). By causality the result is
    exactly what a from-scratch prefill would produce for the suffix
    positions — zero compute for the shared prefix is the whole point.

    tokens: (B, S) suffix tokens, right-padded to a bucket;
    prefix_k/v: (L, B, Hkv, P, dh) logical layout, live below
    ``prefix_len`` (zeros past it — masked inside attention anyway);
    prefix_len: (B,) cached tokens per row; true_len: real suffix
    length per row (``None`` = all of S).

    Returns (logits at the last real suffix token (B, V), suffix K/V
    (L, B, Hkv, S, dh)). GQA-cache families only — the same constraint
    as the paged pool itself.
    """
    if not (cfg.family == "dense"
            or (cfg.family == "moe" and cfg.mla is None)):
        raise ValueError(
            f"suffix prefill needs a token-only GQA cache; family "
            f"{cfg.family} is not supported")
    B, S = tokens.shape
    with jax.named_scope("model.embed"):
        x = params["embed"][tokens]
    plen = jnp.broadcast_to(jnp.asarray(prefix_len, jnp.int32), (B,))
    if true_len is None:
        slen = jnp.full((B,), S, jnp.int32)
    else:
        slen = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32), (B,))

    def body(carry, inp):
        h = carry
        layer, pk_l, pv_l = inp
        with jax.named_scope("model.prefill_attention"):
            hn = rms_norm(h, layer["ln1"], cfg.rms_eps)
            attn_out, k, v = attn_mod.attention_prefill_with_prefix(
                _attn_params(layer), hn, pk_l, pv_l, plen,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                d_head=cfg.head_dim, rope_theta=cfg.rope_theta,
                rms_eps=cfg.rms_eps)
            h = h + attn_out
        with jax.named_scope("model.prefill_mlp"):
            hn = rms_norm(h, layer["ln2"], cfg.rms_eps)
            if cfg.moe is not None:
                ffn, _ = moe_mod.moe_forward(_moe_params(layer), hn,
                                             cfg.moe)
            else:
                m = layer["mlp"]
                ffn = swiglu(hn, m["gate"], m["up"], m["down"])
            return h + ffn, (k, v)

    with jax.named_scope("model.layers"):
        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"],
                                             prefix_k, prefix_v))
    with jax.named_scope("model.head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        last = jnp.take_along_axis(x, (slen - 1)[:, None, None],
                                   axis=1)[:, 0]
        return jnp.einsum("bd,dv->bv", last, head), ks, vs


# ============================================================ decode
class DecodeCache(NamedTuple):
    """Stacked per-layer decode state. Unused fields are size-0 arrays so
    the pytree structure is family-independent under scan.

    ``pk``/``pv`` are the paged warm/cold-tier KV pools of the serving
    fast path (see ``repro.serving.paged_kv``): one shared block pool per
    layer, final physical block a write sentinel. They are size-0 unless
    the cache is created with ``paged_blocks > 0``; when present,
    ``decode_step`` mirrors each appended token into its mapped block
    (``paged_append`` operand) so warm/cold attention reads can go
    through per-request block tables while the dense ``k``/``v`` buffers
    keep serving the hot tier.
    """
    k: jax.Array            # (L, B, Hkv, Smax, dh)  GQA
    v: jax.Array
    ckv: jax.Array          # (L, B, Smax, r)        MLA latent
    krope: jax.Array        # (L, B, Smax, dr)
    conv: jax.Array         # (L, B, ck-1, conv_dim) SSM
    state: jax.Array        # (L, B, H, N, P)
    pk: jax.Array           # (L, NB+1, bs, Hkv, dh) paged KV pool (K)
    pv: jax.Array           # (L, NB+1, bs, Hkv, dh) paged KV pool (V)
    lengths: jax.Array      # (B,) tokens already cached


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                      paged_blocks: int = 0, block_size: int = 0,
                      hot_window: int = 0) -> DecodeCache:
    """Decode cache for ``batch`` sequences of up to ``max_len`` tokens.

    ``paged_blocks``/``block_size`` > 0 additionally allocates the paged
    KV pools (``paged_blocks`` allocatable blocks + 1 sentinel) for the
    serving engine's block-table decode path — GQA-cache families only.

    ``hot_window`` > 0 shrinks the dense ``k``/``v`` buffers to a
    hot-sized RING of that many slots (absolute position p at slot
    ``p % hot_window``): per-slot hot-tier bytes stop scaling with
    ``max_len`` — warm/cold tokens exist only in the paged pools, which
    is why a ring cache requires ``paged_blocks`` (the capacity tier
    backs every evicted token).
    """
    dtype = jnp.dtype(cfg.dtype)
    L = cfg.n_layers
    z = lambda *s: jnp.zeros(s, dtype)
    zf = lambda *s: jnp.zeros(s, jnp.float32)
    # distinct arrays per field: a shared size-0 buffer would be donated
    # twice by the serving engine's donated decode dispatch
    k, v = z(0), z(0)
    ckv, krope = z(0), z(0)
    conv, state = z(0), z(0)
    pk, pv = z(0), z(0)
    if hot_window and not paged_blocks:
        raise ValueError("a hot-window ring cache needs paged pools to "
                         "back evicted tokens (paged_blocks > 0)")
    kv_len = min(hot_window, max_len) if hot_window else max_len
    if paged_blocks:
        if not (cfg.family in ("dense", "vlm")
                or (cfg.family == "moe" and cfg.mla is None)):
            raise ValueError(
                f"paged KV pools require a GQA k/v cache; family "
                f"{cfg.family} stores none")
        pk = z(L, paged_blocks + 1, block_size, cfg.n_kv_heads,
               cfg.head_dim)
        pv = z(L, paged_blocks + 1, block_size, cfg.n_kv_heads,
               cfg.head_dim)
    if cfg.family in ("dense", "vlm"):
        k = z(L, batch, cfg.n_kv_heads, kv_len, cfg.head_dim)
        v = z(L, batch, cfg.n_kv_heads, kv_len, cfg.head_dim)
    elif cfg.family == "moe":
        if cfg.mla is not None:
            ckv = z(L, batch, max_len, cfg.mla.kv_lora_rank)
            krope = z(L, batch, max_len, cfg.mla.qk_rope_head_dim)
        else:
            k = z(L, batch, cfg.n_kv_heads, kv_len, cfg.head_dim)
            v = z(L, batch, cfg.n_kv_heads, kv_len, cfg.head_dim)
    elif cfg.family == "ssm":
        di, H, conv_dim = ssm_mod._dims(cfg.d_model, cfg.ssm)
        conv = z(L, batch, cfg.ssm.conv_kernel - 1, conv_dim)
        state = zf(L, batch, H, cfg.ssm.d_state, cfg.ssm.head_dim)
    elif cfg.family == "hybrid":
        hb = cfg.hybrid
        n_mamba = hb.n_groups * hb.mamba_per_group + hb.tail_mamba
        di, H, conv_dim = ssm_mod._dims(cfg.d_model, cfg.ssm)
        conv = z(n_mamba, batch, cfg.ssm.conv_kernel - 1, conv_dim)
        state = zf(n_mamba, batch, H, cfg.ssm.d_state, cfg.ssm.head_dim)
        # one KV cache per shared-attn application site
        k = z(hb.n_groups, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
        v = z(hb.n_groups, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    else:
        raise ValueError(f"family {cfg.family} has no decode step")
    return DecodeCache(k=k, v=v, ckv=ckv, krope=krope, conv=conv,
                       state=state, pk=pk, pv=pv,
                       lengths=jnp.zeros((batch,), jnp.int32))


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: DecodeCache, *,
                decode_attn_fn: Optional[Callable] = None,
                latent_attn_fn: Optional[Callable] = None,
                paged_append: Optional[tuple] = None
                ) -> tuple[jax.Array, DecodeCache, Optional[jax.Array]]:
    """One autoregressive step. tokens: (B,) int32. Returns
    (logits (B, V), new cache, scores (B, Smax) | None).

    ``decode_attn_fn`` injects the PAM / distributed attention
    implementation. ``scores`` is the layer-mean per-token attention mass
    S_i(j) feeding PAM's importance EMA (None for attention-free archs).

    When the cache carries paged pools (``cache.pk.size > 0``),
    ``paged_append=(dst_block, dst_slot)`` — (B,) physical block + slot
    per sequence, sentinel-routed for inactive rows — must be supplied;
    each layer then mirrors its appended K/V into the pool and
    ``decode_attn_fn`` is called with the per-layer pool slices
    ``(q, k_cache, v_cache, pk, pv, kv_lens)``.
    """
    if not cfg.has_decode:
        raise ValueError(f"{cfg.name} is encoder-only")
    d_fn = decode_attn_fn or attn_mod.dense_decode_attn
    l_fn = latent_attn_fn or mla_mod.mla_latent_decode_attn
    with jax.named_scope("model.embed"):
        x = params["embed"][tokens]                   # (B, d)
    lens = cache.lengths
    scores: Optional[jax.Array] = None
    use_paged = cache.pk.size > 0
    if use_paged and paged_append is None:
        raise ValueError("cache has paged KV pools; decode_step requires "
                         "paged_append=(dst_block, dst_slot)")

    if cfg.family in ("dense", "vlm") or (cfg.family == "moe"
                                          and cfg.mla is None):
        def body(carry, inp):
            h = carry
            if use_paged:
                layer, kc, vc, pk, pv = inp
                paged = (pk, pv) + tuple(paged_append)
            else:
                layer, kc, vc = inp
                paged = None
            with jax.named_scope("model.qkv"):
                hn = rms_norm(h, layer["ln1"], cfg.rms_eps)
            res = attn_mod.attention_decode(
                _attn_params(layer), hn, kc, vc, lens,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                d_head=cfg.head_dim, rope_theta=cfg.rope_theta,
                rms_eps=cfg.rms_eps, decode_attn_fn=d_fn, paged=paged)
            if use_paged:
                attn_out, mass, kc, vc, pk, pv = res
            else:
                attn_out, mass, kc, vc = res
            with jax.named_scope("model.mlp"):
                h = h + attn_out
                hn = rms_norm(h, layer["ln2"], cfg.rms_eps)
                if cfg.moe is not None:
                    ffn, _ = moe_mod.moe_forward(_moe_params(layer),
                                                 hn[:, None], cfg.moe)
                    ffn = ffn[:, 0]
                else:
                    m = layer["mlp"]
                    ffn = swiglu(hn, m["gate"], m["up"], m["down"])
                h = h + ffn
            ys = (kc, vc, pk, pv, mass) if use_paged else (kc, vc, mass)
            return h, ys

        # the scope names the layer loop's own work: slicing each layer's
        # weights, caches and pools out of the stacks, and stacking them
        # back as the loop's outputs
        with jax.named_scope("model.layers"):
            if use_paged:
                x, (k_new, v_new, pk_new, pv_new, masses) = jax.lax.scan(
                    body, x, (params["layers"], cache.k, cache.v,
                              cache.pk, cache.pv))
                cache = cache._replace(k=k_new, v=v_new, pk=pk_new,
                                       pv=pv_new)
            else:
                x, (k_new, v_new, masses) = jax.lax.scan(
                    body, x, (params["layers"], cache.k, cache.v))
                cache = cache._replace(k=k_new, v=v_new)
        with jax.named_scope("pam.mass"):
            scores = jnp.mean(masses, axis=0)

    elif cfg.family == "moe":                          # MLA path
        def body(carry, inp):
            h = carry
            layer, ckv, krp = inp
            hn = rms_norm(h, layer["ln1"], cfg.rms_eps)
            a = layer["mla"]
            attn_out, mass, ckv, krp = mla_mod.mla_decode(
                mla_mod.MLAParams(**a), hn, ckv, krp, lens, cfg.mla,
                n_heads=cfg.n_heads, rope_theta=cfg.rope_theta,
                rms_eps=cfg.rms_eps, latent_attn_fn=l_fn)
            h = h + attn_out
            hn = rms_norm(h, layer["ln2"], cfg.rms_eps)
            ffn, _ = moe_mod.moe_forward(_moe_params(layer), hn[:, None],
                                         cfg.moe)
            return h + ffn[:, 0], (ckv, krp, mass)

        x, (ckv_new, krp_new, masses) = jax.lax.scan(
            body, x, (params["layers"], cache.ckv, cache.krope))
        cache = cache._replace(ckv=ckv_new, krope=krp_new)
        scores = jnp.mean(masses, axis=0)

    elif cfg.family == "ssm":
        def body(carry, inp):
            h = carry
            layer, conv, st = inp
            hn = rms_norm(h, layer["ln"], cfg.rms_eps)
            out, new = ssm_mod.ssm_decode(
                ssm_mod.SSMParams(**layer["ssm"]), hn,
                ssm_mod.SSMCache(conv, st), cfg.ssm, rms_eps=cfg.rms_eps)
            return h + out, (new.conv, new.state)

        x, (conv_new, state_new) = jax.lax.scan(
            body, x, (params["layers"], cache.conv, cache.state))
        cache = cache._replace(conv=conv_new, state=state_new)

    elif cfg.family == "hybrid":
        hb = cfg.hybrid
        npg = hb.mamba_per_group
        shared = params["shared_attn"]

        def mamba_body(carry, inp):
            h = carry
            layer, conv, st = inp
            hn = rms_norm(h, layer["ln"], cfg.rms_eps)
            out, new = ssm_mod.ssm_decode(
                ssm_mod.SSMParams(**layer["ssm"]), hn,
                ssm_mod.SSMCache(conv, st), cfg.ssm, rms_eps=cfg.rms_eps)
            return h + out, (new.conv, new.state)

        n_grp_mamba = hb.n_groups * npg
        conv_g = cache.conv[:n_grp_mamba].reshape(
            (hb.n_groups, npg) + cache.conv.shape[1:])
        state_g = cache.state[:n_grp_mamba].reshape(
            (hb.n_groups, npg) + cache.state.shape[1:])

        def group_body(carry, inp):
            h = carry
            layers, conv, st, kc, vc = inp
            h, (conv, st) = jax.lax.scan(mamba_body, h, (layers, conv, st))
            hn = rms_norm(h, shared["ln1"], cfg.rms_eps)
            attn_out, mass, kc, vc = attn_mod.attention_decode(
                _attn_params(shared), hn, kc, vc, lens,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                d_head=cfg.head_dim, rope_theta=cfg.rope_theta,
                rms_eps=cfg.rms_eps, decode_attn_fn=d_fn)
            h = h + attn_out
            hn = rms_norm(h, shared["ln2"], cfg.rms_eps)
            m = shared["mlp"]
            h = h + swiglu(hn, m["gate"], m["up"], m["down"])
            return h, (conv, st, kc, vc, mass)

        x, (conv_g, state_g, k_new, v_new, masses) = jax.lax.scan(
            group_body, x,
            (params["mamba_groups"], conv_g, state_g, cache.k, cache.v))
        scores = jnp.mean(masses, axis=0)
        x, (conv_t, state_t) = jax.lax.scan(
            mamba_body, x,
            (params["tail_mamba"], cache.conv[n_grp_mamba:],
             cache.state[n_grp_mamba:]))
        cache = cache._replace(
            conv=jnp.concatenate(
                [conv_g.reshape((-1,) + conv_g.shape[2:]), conv_t]),
            state=jnp.concatenate(
                [state_g.reshape((-1,) + state_g.shape[2:]), state_t]),
            k=k_new, v=v_new)
    else:
        raise ValueError(cfg.family)

    with jax.named_scope("model.head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("bd,dv->bv", x, head)
    return logits, cache._replace(lengths=lens + 1), scores
