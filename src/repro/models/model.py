"""Model assembly: pattern-based layer stacks, scan-over-layers, CE loss,
and cached decode — one code path for all ten assigned architectures.

A *pattern* is the repeating unit of the stack (one layer for homogeneous
archs; 8 layers for jamba's 1-attn:7-mamba superblock; DeepSeek's dense
layer 0 is an unrolled prologue).  Per-unit params are stacked along a
leading scan axis so the HLO is O(pattern), not O(depth) — essential for
512-partition compiles.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from .attention import (
    attention,
    attention_decode,
    cross_attention_decode,
    init_attention,
    init_attn_cache,
    precompute_cross_kv,
)
from .config import ArchConfig
from .layers import (
    dense,
    init_mlp,
    init_rms,
    mlp,
    rms_norm,
    shard,
    sinusoidal_positions,
)
from .moe import init_moe, moe_layer
from .ssm import init_ssm, init_ssm_cache, ssm_decode, ssm_layer

__all__ = [
    "LayerSpec", "stack_pattern", "init_params", "forward",
    "lm_loss", "init_cache", "decode_step", "encode",
]

MOE_AUX_WEIGHT = 0.01

# Analysis-only switch: XLA's cost_analysis counts while-loop bodies ONCE,
# so the dry-run's flop/collective census lowers truncated configs with
# scans unrolled (launch/dryrun.py two-point extrapolation).  Production
# lowering always uses rolled scans (compact HLO, fast 512-way compiles).
_SCAN_UNROLL = False


def set_scan_unroll(v: bool):
    global _SCAN_UNROLL
    _SCAN_UNROLL = v


def _unroll():
    return True if _SCAN_UNROLL else 1


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str  # 'attn' | 'ssm'
    mlp: str   # 'dense' | 'moe' | 'none' | 'dense_first'


def stack_pattern(cfg: ArchConfig) -> tuple[list[LayerSpec], list[LayerSpec], int]:
    """(prologue unrolled, scanned pattern, n_scan)."""

    def spec(i: int) -> LayerSpec:
        kind = cfg.layer_kind(i)
        if cfg.is_moe_layer(i):
            m = "moe"
        elif cfg.moe is not None and cfg.moe.first_dense and i == 0:
            m = "dense_first"
        elif cfg.family == "ssm":
            m = "none"  # pure mamba2 block: no separate MLP
        else:
            m = "dense"
        return LayerSpec(kind, m)

    if cfg.attn_period:
        pat = [spec(i) for i in range(cfg.attn_period)]
        assert cfg.n_layers % cfg.attn_period == 0
        return [], pat, cfg.n_layers // cfg.attn_period
    if cfg.moe is not None and cfg.moe.first_dense:
        return [spec(0)], [spec(1)], cfg.n_layers - 1
    return [], [spec(0)], cfg.n_layers


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: ArchConfig, s: LayerSpec, cross: bool) -> dict:
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {"norm1": init_rms(cfg.d_model, cfg.pdtype)}
    if s.kind == "attn":
        p["attn"] = init_attention(ks[0], cfg)
    else:
        p["ssm"] = init_ssm(ks[0], cfg)
    if cross:
        p["norm_x"] = init_rms(cfg.d_model, cfg.pdtype)
        p["cross"] = init_attention(ks[1], cfg, cross=True)
    if s.mlp != "none":
        p["norm2"] = init_rms(cfg.d_model, cfg.pdtype)
        if s.mlp == "moe":
            p["moe"] = init_moe(ks[2], cfg)
        elif s.mlp == "dense_first":
            p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.moe.d_ff_first_dense,
                                cfg.pdtype, cfg.mlp_act)
        else:
            p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.pdtype, cfg.mlp_act)
    return p


def _init_unit(key, cfg: ArchConfig, pattern: list[LayerSpec], cross: bool) -> dict:
    ks = jax.random.split(key, len(pattern))
    return {f"l{i}": _init_layer(ks[i], cfg, s, cross) for i, s in enumerate(pattern)}


def init_params(cfg: ArchConfig, key) -> dict:
    prologue, pattern, n_scan = stack_pattern(cfg)
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model), jnp.float32)
                  * 0.02).astype(cfg.pdtype),
        "final_norm": init_rms(cfg.d_model, cfg.pdtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(keys[1], (cfg.vocab, cfg.d_model), jnp.float32)
                          * 0.02).astype(cfg.pdtype)
    for i, s in enumerate(prologue):
        params[f"pro{i}"] = _init_layer(jax.random.fold_in(keys[2], i), cfg, s, cfg.encdec)
    unit_keys = jax.random.split(keys[3], n_scan)
    params["blocks"] = jax.vmap(
        lambda k: _init_unit(k, cfg, pattern, cfg.encdec)
    )(unit_keys)
    if cfg.encdec:
        enc_keys = jax.random.split(keys[4], cfg.n_enc_layers)
        enc_pattern = [LayerSpec("attn", "dense")]
        params["enc_blocks"] = jax.vmap(
            lambda k: _init_unit(k, cfg, enc_pattern, False)
        )(enc_keys)
        params["enc_norm"] = init_rms(cfg.d_model, cfg.pdtype)
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _apply_layer(x, p, cfg: ArchConfig, s: LayerSpec, positions, mesh, aux,
                 *, causal=True, enc_out=None, use_rope=None):
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if s.kind == "attn":
        mix = attention(h, p["attn"], cfg, positions, causal=causal, use_rope=use_rope)
    else:
        mix, _ = ssm_layer(h, p["ssm"], cfg)
    x = x + mix
    if enc_out is not None:
        h = rms_norm(x, p["norm_x"], cfg.norm_eps)
        x = x + attention(h, p["cross"], cfg, positions, kv_x=enc_out)
    if s.mlp != "none":
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if s.mlp == "moe":
            y, a = moe_layer(h, p["moe"], cfg, mesh=mesh)
            aux = aux + a
        else:
            y = mlp(h, p["mlp"], cfg.mlp_act)
        x = x + y
    return x, aux


def encode(params, frames, cfg: ArchConfig, mesh=None):
    """Whisper-style encoder over stubbed frame embeddings (B, F, d)."""
    x = frames.astype(cfg.adtype)
    x = x + sinusoidal_positions(x.shape[1], cfg.d_model, cfg.adtype)[None]
    spec = LayerSpec("attn", "dense")
    positions = jnp.arange(x.shape[1])

    def body(carry, blk):
        h, _ = _apply_layer(carry, blk["l0"], cfg, spec, positions, mesh,
                            jnp.float32(0.0), causal=False, use_rope=False)
        return h, None

    body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, params["enc_blocks"], unroll=_unroll())
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward(params, tokens, cfg: ArchConfig, *, mesh=None, enc_out=None,
            patch_embeds=None):
    """Token ids (B, S) → logits (B, S, V).  ``enc_out`` feeds cross
    attention (whisper); ``patch_embeds`` (B, Np, d) are spliced in front of
    the token embeddings (llava stub frontend)."""
    prologue, pattern, n_scan = stack_pattern(cfg)
    x = _embed_lookup(params["embed"], tokens, cfg)
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(cfg.adtype), x], axis=1)
    if cfg.encdec:
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model, cfg.adtype)[None]
    x = shard(x, "batch", None, None)
    S = x.shape[1]
    positions = jnp.arange(S)
    aux0 = jnp.float32(0.0)

    aux = aux0
    for i, s in enumerate(prologue):
        x, aux = _apply_layer(x, params[f"pro{i}"], cfg, s, positions, mesh, aux,
                              enc_out=enc_out)

    def body(carry, blk):
        h, a = carry
        for i, s in enumerate(pattern):
            h, a = _apply_layer(h, blk[f"l{i}"], cfg, s, positions, mesh, a,
                                enc_out=enc_out)
        h = shard(h, "batch", None, None)
        return (h, a), None

    body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = lax.scan(body, (x, aux), params["blocks"], unroll=_unroll())

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _lm_head(params, x, cfg)
    return logits, aux


def _lm_head(params, x, cfg: ArchConfig):
    """Vocab-parallel head.  Non-divisible vocabs (whisper 51865, granite
    49155, mamba2 50280) are zero-padded to the model-axis multiple at the
    execution layer and masked to −∞ so CE/argmax semantics are exact; the
    padded lanes keep the (B,S,V)-sized tensor sharded through the loss."""
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    V = head.shape[0]
    M = _ambient_model_axis()
    V_eff = ((V + M - 1) // M) * M
    if V_eff != V:
        head = jnp.concatenate(
            [head, jnp.zeros((V_eff - V, head.shape[1]), head.dtype)], axis=0
        )
    logits = jnp.einsum("bsd,vd->bsv", x, head.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    logits = shard(logits, "batch", None, "vocab")
    if V_eff != V:
        lane = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(lane < V, logits, -1e30)
    return logits


def _ambient_model_axis() -> int:
    from .layers import get_axis_rules

    rules = get_axis_rules()
    if not rules:
        return 1
    return rules.get("pad_to", rules["mesh"].shape.get("model", 1))


def _embed_lookup(table, tokens, cfg: ArchConfig):
    """Token embedding lookup.

    Baseline: plain gather (XLA all-gathers the vocab-sharded table — V·d
    bytes per step).  §Perf knob ``vp_embed``: Megatron vocab-parallel
    lookup under shard_map — each model shard gathers its local vocab
    range, masks, and psums (tokens·d bytes, ≪ V·d for gemma-class vocabs)."""
    from .layers import get_axis_rules

    rules = get_axis_rules()
    V, d = table.shape
    if (not rules or not rules.get("vp_embed")
            or V % rules["mesh"].shape.get("model", 1)):
        return jnp.take(table, tokens, axis=0).astype(cfg.adtype)

    from jax.sharding import PartitionSpec as P

    mesh = rules["mesh"]
    M = mesh.shape["model"]
    V_loc = V // M
    baxes = rules["rules"]["batch"]
    bspec = tuple(baxes) if len(baxes) > 1 else (baxes[0] if baxes else None)

    def local(table_loc, tok):
        me = jax.lax.axis_index("model")
        idx = tok - me * V_loc
        ok = (idx >= 0) & (idx < V_loc)
        out = jnp.take(table_loc, jnp.clip(idx, 0, V_loc - 1), axis=0)
        out = jnp.where(ok[..., None], out.astype(cfg.adtype), 0)
        return jax.lax.psum(out, "model")

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", None), P(bspec, None)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(table, tokens)


def lm_loss(params, batch, cfg: ArchConfig, *, mesh=None):
    """Next-token CE.  batch: {tokens, [frames], [patch_embeds]}."""
    tokens = batch["tokens"]
    enc_out = None
    if cfg.encdec:
        enc_out = encode(params, batch["frames"], cfg, mesh)
    logits, aux = forward(params, tokens, cfg, mesh=mesh, enc_out=enc_out,
                          patch_embeds=batch.get("patch_embeds"))
    n_prefix = 0 if batch.get("patch_embeds") is None else batch["patch_embeds"].shape[1]
    logits = logits[:, n_prefix:]
    targets = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    loss = ce + MOE_AUX_WEIGHT * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def _init_layer_cache(cfg: ArchConfig, s: LayerSpec, B: int, S_ctx: int, dtype,
                      enc_frames: int = 0) -> dict:
    c: dict[str, Any] = {}
    if s.kind == "attn":
        c["kv"] = init_attn_cache(cfg, B, S_ctx, dtype)
    else:
        c["ssm"] = init_ssm_cache(cfg, B, dtype)
    if cfg.encdec:
        K, hd = cfg.n_kv_heads, cfg.hd
        c["cross"] = {
            "k": jnp.zeros((B, enc_frames, K, hd), dtype),
            "v": jnp.zeros((B, enc_frames, K, hd), dtype),
        }
    return c


def init_cache(cfg: ArchConfig, B: int, S_ctx: int, *, dtype=None,
               enc_frames: int = 0) -> dict:
    """Nested decode cache matching the block structure (stacked for scan)."""
    dtype = dtype or cfg.adtype
    prologue, pattern, n_scan = stack_pattern(cfg)
    cache: dict[str, Any] = {}
    for i, s in enumerate(prologue):
        cache[f"pro{i}"] = _init_layer_cache(cfg, s, B, S_ctx, dtype, enc_frames)

    def one_unit(_):
        return {f"l{i}": _init_layer_cache(cfg, s, B, S_ctx, dtype, enc_frames)
                for i, s in enumerate(pattern)}

    cache["blocks"] = jax.vmap(one_unit)(jnp.arange(n_scan))
    return cache


def _decode_layer(x, p, c, cfg: ArchConfig, s: LayerSpec, pos, mesh):
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    new_c = dict(c)
    if s.kind == "attn":
        mix, new_c["kv"] = attention_decode(h, p["attn"], cfg, c["kv"], pos)
    else:
        mix, new_c["ssm"] = ssm_decode(h, p["ssm"], cfg, c["ssm"])
    x = x + mix
    if cfg.encdec:
        h = rms_norm(x, p["norm_x"], cfg.norm_eps)
        x = x + cross_attention_decode(h, p["cross"], cfg, c["cross"])
    if s.mlp != "none":
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if s.mlp == "moe":
            y, _ = moe_layer(h, p["moe"], cfg, mesh=mesh)
        else:
            y = mlp(h, p["mlp"], cfg.mlp_act)
        x = x + y
    return x, new_c


def decode_step(params, cache, token, pos, cfg: ArchConfig, *, mesh=None):
    """One decode step: token (B, 1) int32, scalar pos → (logits (B, V), cache)."""
    prologue, pattern, n_scan = stack_pattern(cfg)
    x = jnp.take(params["embed"], token, axis=0).astype(cfg.adtype)
    if cfg.encdec:
        x = x + _sin_at(pos, cfg.d_model, cfg.adtype)

    new_cache: dict[str, Any] = {}
    for i, s in enumerate(prologue):
        x, new_cache[f"pro{i}"] = _decode_layer(
            x, params[f"pro{i}"], cache[f"pro{i}"], cfg, s, pos, mesh
        )

    def body(carry, xs):
        h = carry
        blk, c = xs
        cs = {}
        for i, s in enumerate(pattern):
            h, cs[f"l{i}"] = _decode_layer(h, blk[f"l{i}"], c[f"l{i}"], cfg, s, pos, mesh)
        return h, cs

    x, new_blocks = lax.scan(body, x, (params["blocks"], cache["blocks"]),
                             unroll=_unroll())
    new_cache["blocks"] = new_blocks

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _lm_head(params, x, cfg)
    return logits[:, 0], new_cache


def _sin_at(pos, d, dtype):
    i = jnp.arange(d // 2, dtype=jnp.float32)
    angle = pos.astype(jnp.float32) / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)])[None, None, :].astype(dtype)


def prefill_cross_cache(params, enc_out, cfg: ArchConfig, cache):
    """Fill the decode cache's cross-attention K/V from an encoder pass
    (whisper serving: encoder runs once per request, decode reuses)."""
    if not cfg.encdec:
        return cache
    prologue, pattern, _ = stack_pattern(cfg)
    new_cache = dict(cache)

    def unit_fn(blk):
        return {f"l{i}": precompute_cross_kv(enc_out, blk[f"l{i}"]["cross"], cfg)
                for i, _s in enumerate(pattern)}

    cross = jax.vmap(unit_fn)(params["blocks"])
    nb = {}
    for key, layer_cache in cache["blocks"].items():
        nv = dict(layer_cache)
        if key in cross:
            nv["cross"] = cross[key]
        nb[key] = nv
    new_cache["blocks"] = nb
    for i, _s in enumerate(prologue):
        pc = dict(new_cache[f"pro{i}"])
        pc["cross"] = precompute_cross_kv(enc_out, params[f"pro{i}"]["cross"], cfg)
        new_cache[f"pro{i}"] = pc
    return new_cache
