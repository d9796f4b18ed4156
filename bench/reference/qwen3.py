"""Plain Qwen3 reference for the served-token check, in float32.

Follows the published Qwen3 decoder (Qwen/Qwen3-0.6B and Qwen/Qwen3-14B
``config.json``): RMSNorm pre-norm blocks, grouped-query attention with a
per-head RMSNorm on queries and keys before rotary embedding (half-split,
``rope_theta``), SwiGLU MLP, final RMSNorm, tied or untied head. Matrix
products run at full float32 precision. It imports nothing of the serving
program: it reads the weight tree the benchmark made from the seed, the
prompts, and the tokens the program streamed.

Decode attention reads the tokens that PAM lets participate, replayed
here from PAM's published rules (paper §6.3, eq. 7): at admission a
prompt token at distance ``t`` from the end has importance ``1/(1+t)``;
each step the last ``recency_window`` tokens and the top
``max(len // compression, 1)`` tokens by importance participate (ties go
to the earlier position); after the step each token's importance becomes
``lam * mass + (1 - lam) * importance``, where ``mass`` is its attention
probability averaged over heads and layers times the number of
participating tokens, and the token just appended takes the largest
importance of its row. Prefill attention is dense and causal.

The reference also keeps each token's ``share``: the part of its
importance that attention mass made (the rest is the exact admission
prior). A program that computes the mass in another precision holds an
importance within a small multiple of that share of the reference's, so a
step at which some chosen and some dropped token lie closer than
``rtol`` times their shares is open to rounding: from there the program
may attend over other tokens, and later positions are not compared.

A control runs the same model in a lower precision, int8 or fp8 (e4m3):
weights scaled per output channel, activations per token and the K/V
cache per token and head. On the same tokens, with its own
participation, it picks the token it puts first at each position, and
the float32 reference reads that token's gap.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# the lower precisions of the control: the largest code of each, and the
# type its codes take
_CODES = {"int8": (127.0, jnp.int8), "fp8": (448.0, jnp.float8_e4m3fn)}


@functools.partial(jax.jit, static_argnames=("axis", "kind"))
def _qz(x, axis, kind):
    """Symmetric ``kind`` codes of ``x`` scaled along ``axis``, and the
    scales: int8 rounds to the nearest step, fp8 (e4m3) keeps three bits of
    mantissa."""
    top, dtype = _CODES[kind]
    x = x.astype(F32)
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s > 0, s, 1.0)
    y = jnp.clip(x / s, -top, top)
    return (jnp.rint(y) if kind == "int8" else y).astype(dtype), s


def _dq(codes, s):
    return codes.astype(F32) * s


def _mm(x, w, quant):
    """x (..., din) @ w (din, dout); ``w`` is float32-castable or, under
    ``quant`` (a kind of ``_CODES``), a (codes, scales) pair and ``x`` is
    quantized per token."""
    if quant:
        x = _dq(*_qz(x, -1, quant))
        w = _dq(*w)
    return jnp.einsum("...i,io->...o", x, w.astype(F32), precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, pos, theta):
    """x (..., S, H, d) or (..., H, d) with pos broadcastable to x[..., 0, 0]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[..., None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


class _Model:
    """Static sizes of one configuration."""

    def __init__(self, hf):
        self.d = hf["hidden_size"]
        self.h = hf["num_attention_heads"]
        self.kv = hf["num_key_value_heads"]
        self.dh = hf["head_dim"]
        self.L = hf["num_hidden_layers"]
        self.eps = float(hf["rms_norm_eps"])
        self.theta = float(hf["rope_theta"])
        self.tied = bool(hf["tie_word_embeddings"])

    def layer(self, p, l):
        return jax.tree.map(lambda a: a[l], p["layers"])

    def head(self, p):
        return p["embed"].T if self.tied else p["lm_head"]


def _quantize_params(m: _Model, p, kind):
    """Weights as ``kind`` (codes, per-output-channel scales); norms and
    the embedding table (a lookup, no product) stay as they are."""
    def q(w):
        return _qz(w, -2, kind)
    lay = p["layers"]
    out = {"embed": p["embed"], "final_norm": p["final_norm"],
           "head": q(m.head(p)),
           "layers": {"ln1": lay["ln1"], "ln2": lay["ln2"],
                      "attn": {k: (q(v) if k.startswith("w") else v)
                               for k, v in lay["attn"].items()},
                      "mlp": {k: q(v) for k, v in lay["mlp"].items()}}}
    return out


def _head_of(m, p, quant):
    return p["head"] if quant else m.head(p)


def _block_ffn(m, lp, x, quant):
    h = _rms(x, lp["ln2"], m.eps)
    mp = lp["mlp"]
    g = _mm(h, mp["gate"], quant)
    u = _mm(h, mp["up"], quant)
    return x + _mm(jax.nn.silu(g) * u, mp["down"], quant)


def _qkv(m, lp, h, pos, quant):
    a = lp["attn"]
    lead = h.shape[:-1]
    q = _mm(h, a["wq"], quant).reshape(lead + (m.h, m.dh))
    k = _mm(h, a["wk"], quant).reshape(lead + (m.kv, m.dh))
    v = _mm(h, a["wv"], quant).reshape(lead + (m.kv, m.dh))
    q = _rope(_rms(q, a["q_norm"], m.eps), pos, m.theta)
    k = _rope(_rms(k, a["k_norm"], m.eps), pos, m.theta)
    return q, k, v


def _kv_store(x, quant):
    """What the cache keeps of K or V (B, S, Hkv, d): float32, or ``quant``
    codes per (token, head) dequantized on read."""
    return _dq(*_qz(x, -1, quant)) if quant else x


@functools.partial(jax.jit, static_argnames=("hf_items", "quant", "s_max",
                                             "chunk"))
def _prefill(params, tokens, plen, *, hf_items, quant, s_max, chunk):
    """Dense causal prefill of right-padded prompts (B, P). Returns
    (K, V caches (L, B, Hkv, s_max, d), logits at each prompt's last
    token (B, V))."""
    m = _Model(dict(hf_items))
    B, P = tokens.shape
    rep = m.h // m.kv
    pos = jnp.broadcast_to(jnp.arange(P), (B, P))
    x = params["embed"][tokens].astype(F32)
    kc = jnp.zeros((m.L, B, m.kv, s_max, m.dh), F32)
    vc = jnp.zeros_like(kc)
    nq = P // chunk

    def layer(l, carry):
        x, kc, vc = carry
        lp = m.layer(params, l)
        h = _rms(x, lp["ln1"], m.eps)
        q, k, v = _qkv(m, lp, h, pos, quant)
        k, v = _kv_store(k, quant), _kv_store(v, quant)
        kh = jnp.moveaxis(k, 2, 1)                     # (B, Hkv, P, d)
        vh = jnp.moveaxis(v, 2, 1)
        qg = q.reshape(B, nq, chunk, m.kv, rep, m.dh)

        def one(i):
            qi = qg[:, i]                              # (B, c, Hkv, rep, d)
            s = jnp.einsum("bcgrd,bgsd->bgrcs", qi, kh,
                           precision=HIGHEST) / math.sqrt(m.dh)
            qpos = i * chunk + jnp.arange(chunk)
            s = jnp.where(jnp.arange(P)[None, :] <= qpos[:, None], s,
                          -jnp.inf)
            pr = jax.nn.softmax(s, -1)
            o = jnp.einsum("bgrcs,bgsd->bcgrd", pr, vh, precision=HIGHEST)
            return o.reshape(B, chunk, m.h * m.dh)

        att = jax.lax.map(one, jnp.arange(nq))         # (nq, B, c, H*d)
        att = jnp.moveaxis(att, 0, 1).reshape(B, P, m.h * m.dh)
        x = x + _mm(att, lp["attn"]["wo"], quant)
        x = _block_ffn(m, lp, x, quant)
        kc = kc.at[l, :, :, :P].set(kh)
        vc = vc.at[l, :, :, :P].set(vh)
        return x, kc, vc

    x, kc, vc = jax.lax.fori_loop(0, m.L, layer, (x, kc, vc))
    last = jnp.take_along_axis(x, (plen - 1)[:, None, None], 1)[:, 0]
    last = _rms(last, params["final_norm"], m.eps)
    logits = _mm(last, _head_of(m, params, quant), quant)
    return kc, vc, logits


def _participation(imp, share, length, compression, recency, rtol):
    """(B, S) participation mask for rows of ``length`` tokens, and (B,)
    whether the cut is open to rounding: whether some chosen token and
    some token left out come within ``rtol`` times their ``share`` (the
    part of their importance that attention mass made) of each other."""
    S = imp.shape[1]
    pos = jnp.arange(S)[None, :]
    valid = pos < length[:, None]
    budget = jnp.maximum(length // compression, 1)
    recent = valid & (pos >= (length - recency)[:, None])
    score = jnp.where(valid, imp, -jnp.inf)
    score = jnp.where(recent, jnp.inf, score)
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.zeros_like(order).at[
        jnp.arange(imp.shape[0])[:, None], order].set(pos + 0 * order)
    chosen = valid & (rank < budget[:, None])
    ranked = chosen & ~recent
    left = valid & ~chosen & ~recent
    low = jnp.min(jnp.where(ranked, imp - rtol * share, jnp.inf), -1)
    high = jnp.max(jnp.where(left, imp + rtol * share, -jnp.inf), -1)
    return chosen | recent, low <= high


def _decode_one(m, params, quant, kc, vc, tok, pos, imp, share, pam, rtol):
    """One step for every row: token ``tok`` at position ``pos``.
    Returns (logits, kc, vc, importance, share, whether the step's
    participation was open to rounding)."""
    B = tok.shape[0]
    rep = m.h // m.kv
    length = pos + 1
    part, open_cut = _participation(imp, share, length, pam["compression"],
                                    pam["recency_window"], rtol)
    n_live = jnp.sum(part, -1, keepdims=True).astype(F32)
    x = params["embed"][tok].astype(F32)
    bidx = jnp.arange(B)

    def layer(l, carry):
        x, kc, vc, mass = carry
        lp = m.layer(params, l)
        h = _rms(x, lp["ln1"], m.eps)
        q, k, v = _qkv(m, lp, h, pos, quant)           # (B, H|Hkv, d)
        kc = kc.at[l, bidx, :, pos].set(_kv_store(k, quant))
        vc = vc.at[l, bidx, :, pos].set(_kv_store(v, quant))
        kl, vl = kc[l], vc[l]                           # (B, Hkv, S, d)
        s = jnp.einsum("bgrd,bgsd->bgrs", q.reshape(B, m.kv, rep, m.dh), kl,
                       precision=HIGHEST) / math.sqrt(m.dh)
        s = jnp.where(part[:, None, None, :], s, -jnp.inf)
        pr = jax.nn.softmax(s, -1)
        o = jnp.einsum("bgrs,bgsd->bgrd", pr, vl, precision=HIGHEST)
        x = x + _mm(o.reshape(B, m.h * m.dh), lp["attn"]["wo"], quant)
        x = _block_ffn(m, lp, x, quant)
        return x, kc, vc, mass + jnp.mean(pr, (1, 2)) * n_live

    mass0 = jnp.zeros(imp.shape, F32)
    x, kc, vc, mass = jax.lax.fori_loop(0, m.L, layer, (x, kc, vc, mass0))
    mass = mass / m.L
    valid = jnp.arange(imp.shape[1])[None, :] < length[:, None]
    lam = pam["lam"]
    mass = jnp.where(valid, mass, 0.0)
    imp = lam * mass + (1.0 - lam) * imp
    share = lam * mass + (1.0 - lam) * share
    imp = imp.at[bidx, pos].set(jnp.maximum(imp[bidx, pos],
                                            jnp.max(imp, -1)))
    share = share.at[bidx, pos].set(jnp.max(share, -1))
    x = _rms(x, params["final_norm"], m.eps)
    return (_mm(x, _head_of(m, params, quant), quant), kc, vc, imp, share,
            open_cut)


def _gap(ref_logits, tok):
    """How far the reference puts ``tok`` below its own best."""
    return jnp.max(ref_logits, -1) - jnp.take_along_axis(
        ref_logits, tok[:, None], -1)[:, 0]


@functools.partial(jax.jit, static_argnames=("hf_items", "pam_items",
                                             "quant", "rtol"),
                   donate_argnums=(1,))
def _decode(params, state, served, plen, alen, cands, *, hf_items,
            pam_items, quant, rtol):
    """Teacher-forced decode over the served tokens (B, A).

    Float32 (``quant`` empty): returns the gap of each candidate token
    array of ``cands`` (K, B, A) at each position after the first,
    (K, B, A), and whether the participation behind each position was
    open to rounding (B, A). A lower precision (``quant`` a kind of
    ``_CODES``): returns the tokens the model puts first at each position
    (B, A)."""
    m = _Model(dict(hf_items))
    pam = dict(pam_items)
    B, A = served.shape
    steps = jnp.max(alen) - 1

    def step(j, carry):
        st, out, opened = carry
        pos = plen + j
        act = (j + 1) < alen
        logits, kc, vc, imp, share, open_cut = _decode_one(
            m, params, quant, st["kc"], st["vc"], served[:, j], pos,
            st["imp"], st["share"], pam, rtol)
        st = {"kc": kc, "vc": vc, "imp": imp, "share": share}
        opened = opened.at[:, j + 1].set(open_cut & act)
        if quant:
            pick = jnp.argmax(logits, -1).astype(jnp.int32)
            out = out.at[:, j + 1].set(jnp.where(act, pick, 0))
        else:
            nxt = cands[:, :, jnp.minimum(j + 1, A - 1)]     # (K, B)
            g = jax.vmap(lambda t: _gap(logits, t))(nxt)
            out = out.at[:, :, j + 1].set(jnp.where(act[None], g, 0.0))
        return st, out, opened

    if quant:
        out0 = jnp.zeros((B, A), jnp.int32)
    else:
        out0 = jnp.zeros((cands.shape[0], B, A), F32)
    _, out, opened = jax.lax.fori_loop(
        0, steps, step, (state, out0, jnp.zeros((B, A), bool)))
    return out if quant else (out, opened)


def _initial_state(kc, vc, plen, s_max):
    pos = jnp.arange(s_max)[None, :]
    dist = (plen[:, None] - 1 - pos).astype(F32)
    imp = jnp.where(pos < plen[:, None], 1.0 / (1.0 + dist), 0.0)
    return {"kc": kc, "vc": vc, "imp": imp, "share": jnp.zeros_like(imp)}


def served_token_gaps(hf: dict, params_fn, prompts, served, *, pam: dict,
                      rtol: float, p_pad: int, s_max: int, a_max: int,
                      controls: tuple = (), chunk: int = 512) -> dict:
    """The gap, at each served position, by which a served token's
    reference logit lies below the reference's best, and per request the
    positions compared: those before the first whose participation is
    open to rounding.

    PAM keeps the top tokens by an importance that attention mass feeds.
    Where a token it keeps and one it drops lie within ``rtol`` times the
    mass-made part of their importance of each other, a program that
    computes the mass in another precision may keep the other one; from
    there on the two attend over different tokens, and the request's
    later positions are not compared (``compared`` counts the positions
    that are).

    ``params_fn()`` returns the weight tree. ``prompts``/``served``: lists
    of int arrays, padded to ``p_pad`` (a multiple of ``chunk``),
    ``s_max`` and ``a_max`` so that one compiled program serves every run
    of a cell. Each kind in ``controls`` ("int8", "fp8") runs the model in
    that precision first (alone on the device) to pick its own tokens,
    and ``control_by_position[kind]`` holds their gaps under the float32
    reference."""
    B = len(prompts)
    if p_pad % chunk:
        raise ValueError(f"p_pad {p_pad} is not a multiple of {chunk}")
    toks = np.zeros((B, p_pad), np.int32)
    srv = np.zeros((B, a_max), np.int32)
    plen = np.zeros((B,), np.int32)
    alen = np.zeros((B,), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        if len(p) > p_pad or len(s) > a_max or len(p) + len(s) > s_max:
            raise ValueError(f"request {i} ({len(p)}+{len(s)} tokens) does "
                             f"not fit the reference's shapes")
        toks[i, :len(p)], srv[i, :len(s)] = p, s
        plen[i], alen[i] = len(p), len(s)
    items = tuple(sorted((k, v) for k, v in hf.items()
                         if isinstance(v, (int, float, str, bool))))
    pam_items = tuple(sorted(pam.items()))
    toks, srv = jnp.asarray(toks), jnp.asarray(srv)
    plen_d, alen_d = jnp.asarray(plen), jnp.asarray(alen)
    cands = [srv]
    with jax.default_matmul_precision("highest"):
        for kind in controls:
            qparams = _quantize_params(_Model(dict(items)), params_fn(), kind)
            kc, vc, clog = _prefill(qparams, toks, plen_d, hf_items=items,
                                    quant=kind, s_max=s_max, chunk=chunk)
            picks = _decode(qparams, _initial_state(kc, vc, plen_d, s_max),
                            srv, plen_d, alen_d, None, hf_items=items,
                            pam_items=pam_items, quant=kind, rtol=0.0)
            picks = picks.at[:, 0].set(jnp.argmax(clog, -1).astype(
                jnp.int32))
            cands.append(picks)
            del qparams, kc, vc, clog
        params = params_fn()
        kc, vc, logits = _prefill(params, toks, plen_d, hf_items=items,
                                  quant="", s_max=s_max, chunk=chunk)
        cands = jnp.stack(cands)
        first = jax.vmap(lambda t: _gap(logits, t))(cands[:, :, 0])
        per_pos, opened = _decode(
            params, _initial_state(kc, vc, plen_d, s_max), srv, plen_d,
            alen_d, cands, hf_items=items, pam_items=pam_items, quant="",
            rtol=float(rtol))
        per_pos = per_pos.at[:, :, 0].set(first)
    per_pos, opened = np.asarray(per_pos), np.asarray(opened)  # (K, B, A)
    compared = np.where(opened.any(-1), opened.argmax(-1), alen)
    return {"by_position": per_pos[0], "compared": compared,
            "control_by_position": dict(zip(controls, per_pos[1:]))}


def compare(by_position, compared) -> dict:
    """The numbers the check holds to limits, over the compared positions
    of every request: the widest gap and the mean gap."""
    keep = np.arange(by_position.shape[-1])[None, :] < np.asarray(
        compared)[:, None]
    vals = by_position[keep]
    return {"max_logit_gap": float(vals.max()) if vals.size else 0.0,
            "mean_logit_gap": float(vals.mean()) if vals.size else 0.0}
