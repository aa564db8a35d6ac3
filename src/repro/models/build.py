"""Generic segment-based model builder.

A model is a sequence of *segments*; each uniform segment stacks its
layers' params with a leading layer dim and executes with ``lax.scan``
(compact HLO even for 81-layer models).  Periodic patterns (gemma3's
5-local:1-global, zamba2's 5-mamba:1-shared-attn) collapse into a
``group`` segment — an outer scan over groups whose body runs the inner
segments (the weight-shared attention block's params are closed over as
scan constants, which is exactly weight sharing).

Block kinds: dense / moe / mamba / encoder / local / global /
shared_attn / mla / mla_dense.  One code path serves every architecture.

Each block also returns an ``aux`` vector: the MoE balance loss, then
the held experts' load (assignments computed, experts hit), summed
over layers.  ``chunk_step`` and the decode blocks of a model with an
expert layer return the load as a last output; other models return
none.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.kernels import ops
from repro.models import attention as attn
from repro.models import mamba2, moe
from repro.models.common import (
    cross_entropy,
    embed,
    init_dense,
    rms_norm,
    split_keys,
    swiglu,
)

ATTN_KINDS = ("dense", "moe", "encoder", "local", "global", "shared_attn",
              "mla", "mla_dense")
MLA_KINDS = ("mla", "mla_dense")
MOE_KINDS = ("moe", "mla")
N_AUX = 3   # balance loss, expert_tokens, experts_hit


def _no_aux():
    return jnp.zeros((N_AUX,), jnp.float32)


def _at_last(x, lens):
    """Each row's hidden state at its last valid position."""
    idx = jnp.clip(lens - 1, 0, x.shape[1] - 1)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]


def _stops(tok, new_pos, new_rem, eos, max_len):
    """The decode programs' stopping predicate, on the device, after a
    token is appended (host twin: ``InferenceEngine._is_done``): output
    cap reached, EOS emitted, or no room for another token's KV within
    ``max_len``."""
    return (new_rem <= 0) | (tok == eos) | (new_pos + 1 >= max_len)


def _at_precision(fn):
    """Trace ``fn`` under the model's ``matmul_precision``."""
    @functools.wraps(fn)
    def traced(self, *args, **kwargs):
        if self.cfg.matmul_precision is None:
            return fn(self, *args, **kwargs)
        with jax.default_matmul_precision(self.cfg.matmul_precision):
            return fn(self, *args, **kwargs)
    return traced


@dataclasses.dataclass(frozen=True)
class SegSpec:
    kind: str  # block kind, or "group"
    count: int
    inner: Optional[tuple] = None  # for groups: ((kind, count), ...)


def build_segments(cfg: ModelConfig) -> list[SegSpec]:
    pattern = list(cfg.layer_pattern())
    if len(pattern) >= 4 and pattern[0][0] != pattern[1][0]:
        pair = (pattern[0], pattern[1])
        n_rep = 0
        while (
            2 * n_rep + 1 < len(pattern)
            and (pattern[2 * n_rep], pattern[2 * n_rep + 1]) == pair
        ):
            n_rep += 1
        if n_rep >= 2:
            segs = [SegSpec("group", n_rep, inner=pair)]
            segs += [SegSpec(k, c) for k, c in pattern[2 * n_rep:]]
            return segs
    return [SegSpec(k, c) for k, c in pattern]


# ---------------------------------------------------------------------------
# Per-block params
# ---------------------------------------------------------------------------


def _init_block(cfg: ModelConfig, key, kind: str, dtype):
    if kind == "mamba":
        k1, = split_keys(key, 1)
        return {
            "mamba": mamba2.init_mamba(cfg, k1, dtype),
            "ln": jnp.zeros((cfg.d_model,), dtype),
        }
    k1, k2, k3, k4, k5 = split_keys(key, 5)
    p = {
        "attn": (attn.init_mla(cfg, k1, dtype) if kind in MLA_KINDS
                 else attn.init_attn(cfg, k1, dtype)),
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
    }
    if kind in MOE_KINDS:
        p["moe"] = moe.init_moe(cfg, k2, dtype)
    else:
        d, f = cfg.d_model, cfg.d_ff
        p["ffn"] = {
            "w_gate": init_dense(k3, (d, f), dtype=dtype),
            "w_up": init_dense(k4, (d, f), dtype=dtype),
            "w_down": init_dense(k5, (f, d), dtype=dtype),
        }
    return p


def _block_axes(cfg: ModelConfig, kind: str, n_lead: int):
    """Logical-axis tree matching _init_block's param tree."""
    lead = ("layers",) * n_lead
    if kind == "mamba":
        return {
            "mamba": {
                k: lead + tuple(v)
                for k, v in mamba2.MAMBA_PARAM_AXES.items()
            },
            "ln": lead + (None,),
        }
    if kind in MLA_KINDS:
        attn_axes = {k: attn.MLA_PARAM_AXES[k]
                     for k in attn.mla_param_shapes(cfg)}
    else:
        attn_axes = {k: v for k, v in attn.ATTN_PARAM_AXES.items()
                     if cfg.qkv_bias or not k.startswith("b")}
    out = {
        "attn": {k: lead + tuple(v) for k, v in attn_axes.items()},
        "ln1": lead + (None,),
        "ln2": lead + (None,),
    }
    if kind in MOE_KINDS:
        out["moe"] = {
            k: lead + tuple(v) for k, v in moe.moe_param_axes(cfg).items()
        }
    else:
        out["ffn"] = {
            "w_gate": lead + ("fsdp", "ff"),
            "w_up": lead + ("fsdp", "ff"),
            "w_down": lead + ("ff", "fsdp"),
        }
    return out


def _stack_init(cfg, key, kind, dtype, lead: tuple[int, ...]):
    """Init `prod(lead)` blocks and reshape leading dims to `lead`."""
    n = 1
    for x in lead:
        n *= x
    keys = jnp.stack(split_keys(key, n))
    flat = jax.vmap(lambda k: _init_block(cfg, k, kind, dtype))(keys)
    if len(lead) == 1:
        return flat
    return jax.tree.map(
        lambda a: a.reshape(lead + a.shape[1:]), flat
    )


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    kv_repeat: int = 1
    remat: bool = False
    q_chunk: int = 512
    # Pad the embedding/vocab dim (Megatron-style) so it shards over the
    # model axis; labels never index the pad ids.
    vocab_pad: int = 0
    # Unroll layer stacks instead of lax.scan.  Scan keeps HLO compact
    # for real runs; the dry-run unrolls so cost_analysis() and the
    # collective-bytes parse see every layer (XLA's cost model counts a
    # loop body once, not trip_count times).
    unroll: bool = False

    def __post_init__(self):
        self.segments = build_segments(self.cfg)
        self.has_shared = "shared_attn" in self.block_kinds
        # the held experts' load rides out of the step programs
        self.counts_experts = bool(self.block_kinds & set(MOE_KINDS))

    @property
    def block_kinds(self) -> set:
        kinds = set()
        for s in self.segments:
            if s.kind == "group":
                kinds.update(k for k, _ in s.inner)
            else:
                kinds.add(s.kind)
        return kinds

    # -- init ---------------------------------------------------------------
    def init(self, key) -> dict:
        cfg, dt = self.cfg, self.param_dtype
        keys = split_keys(key, len(self.segments) + 3)
        v = cfg.vocab_size + self.vocab_pad
        params: dict[str, Any] = {
            "embed": init_dense(keys[0], (v, cfg.d_model), dtype=dt),
            "final_norm": jnp.zeros((cfg.d_model,), dt),
        }
        if not cfg.tie_embeddings:
            params["head"] = init_dense(keys[1], (v, cfg.d_model), dtype=dt)
        if self.has_shared:
            params["shared"] = _init_block(cfg, keys[2], "dense", dt)
        seg_params = []
        for spec, k in zip(self.segments, keys[3:]):
            if spec.kind == "group":
                sub = {}
                sks = split_keys(k, len(spec.inner))
                for (ikind, icount), sk in zip(spec.inner, sks):
                    if ikind == "shared_attn":
                        continue
                    sub[ikind] = _stack_init(
                        cfg, sk, ikind, dt, (spec.count, icount)
                    )
                seg_params.append(sub)
            elif spec.kind == "shared_attn":
                seg_params.append({})
            else:
                seg_params.append(
                    _stack_init(cfg, k, spec.kind, dt, (spec.count,))
                )
        params["segments"] = seg_params
        return params

    def param_axes(self) -> dict:
        cfg = self.cfg
        axes: dict[str, Any] = {
            "embed": ("vocab", "fsdp"),
            "final_norm": (None,),
        }
        if not cfg.tie_embeddings:
            axes["head"] = ("vocab", "fsdp")
        if self.has_shared:
            axes["shared"] = _block_axes(cfg, "dense", 0)
        seg_axes = []
        for spec in self.segments:
            if spec.kind == "group":
                seg_axes.append({
                    ikind: _block_axes(cfg, ikind, 2)
                    for ikind, _ in spec.inner
                    if ikind != "shared_attn"
                })
            elif spec.kind == "shared_attn":
                seg_axes.append({})
            else:
                seg_axes.append(_block_axes(cfg, spec.kind, 1))
        axes["segments"] = seg_axes
        return axes

    def abstract_params(self) -> dict:
        return jax.eval_shape(self.init, jax.random.key(0))

    # -- block bodies ---------------------------------------------------------
    def _attn_block(self, bp, x, kind, *, positions, lens, cache,
                    make_cache, cache_len, decode, chunked=False,
                    page_table=None):
        cfg = self.cfg
        if kind in MLA_KINDS:
            return self._mla_block(
                bp, x, kind, positions=positions, lens=lens, cache=cache,
                make_cache=make_cache, decode=decode, chunked=chunked,
                page_table=page_table,
            )
        window = cfg.window if kind == "local" else 0
        causal = cfg.causal
        use_rope = cfg.frontend != "frames"
        h = rms_norm(x, bp["ln1"], cfg.norm_eps)
        q, k, v = attn.project_qkv(
            bp["attn"], h, cfg, positions=positions,
            kv_repeat=self.kv_repeat, use_rope=use_rope,
        )
        new_cache = None
        if chunked:
            # paged plane: write the chunk's K/V into the page pool,
            # then attend causally over [0, start + chunk_len)
            b, s = x.shape[:2]
            valid = jnp.arange(s)[None, :] < lens[:, None]
            kp, vp = attn.update_paged_cache(
                cache["k_pages"], cache["v_pages"], page_table, k, v,
                positions, valid,
            )
            kv_len = positions[:, 0] + lens
            if s == 1 and ops.kernels_enabled():
                # the one kernel decision (see repro.kernels.ops); GQA
                # is handled inside the kernel — a KV head's query heads
                # read its pages together, the pool stays at Hkv heads
                ctx = ops.paged_decode_attention(
                    q[:, :, 0, :], kp, vp, page_table, kv_len,
                )[:, :, None, :]
            else:
                ctx = attn.paged_chunk_attention(
                    q, kp, vp, page_table, q_pos=positions,
                    kv_len=kv_len, causal=causal,
                )
            new_cache = {"k_pages": kp, "v_pages": vp}
        elif decode:
            kc, vc, kv_pos = attn.update_cache(
                cache["k"], cache["v"], cache["pos"], k, v, positions[:, 0],
                window=window,
            )
            # slot plane: jnp only — its kernels' alignment needs are
            # broken by padded prefill (see repro.kernels.ops)
            ctx = attn.decode_attention(
                q, kc, vc, q_pos=positions[:, 0], kv_pos=kv_pos,
                kv_len=lens, causal=causal, window=window,
            )
            new_cache = {"k": kc, "v": vc, "pos": kv_pos}
        else:
            ctx = attn.chunked_attention(
                q, k, v, lens=lens, causal=causal, window=window,
                q_chunk=self.q_chunk, unroll=self.unroll,
            )
            if make_cache:
                new_cache = self._build_cache(k, v, lens, window, cache_len)
        b, s = x.shape[:2]
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)
        o = ctx @ bp["attn"]["wo"].astype(x.dtype)
        x = x + o
        valid = (jnp.arange(x.shape[1])[None, :] < lens[:, None]
                 if chunked else None)
        x, aux = self._ffn_block(bp, x, kind, valid)
        return x, new_cache, aux

    def _ffn_block(self, bp, x, kind, valid):
        """The block's second half: x -> (x + FFN(norm(x)), aux)."""
        cfg = self.cfg
        h = rms_norm(x, bp["ln2"], cfg.norm_eps)
        aux = _no_aux()
        if kind in MOE_KINDS:
            y, a = moe.moe_ffn(bp["moe"], h, cfg, valid=valid)
            aux = jnp.stack([a["lb_loss"],
                             a["expert_tokens"].astype(jnp.float32),
                             a["experts_hit"].astype(jnp.float32)])
        else:
            f = bp["ffn"]
            y = swiglu(h, f["w_gate"].astype(x.dtype),
                       f["w_up"].astype(x.dtype),
                       f["w_down"].astype(x.dtype))
        y = constrain(y, "batch", "seq", "embed")
        out = constrain(x + y, "batch", "seq", "residual")
        return out, aux

    def _mla_block(self, bp, x, kind, *, positions, lens, cache,
                   make_cache, decode, chunked, page_table):
        """Latent attention (absorbed form, see ``attention.py``), then
        the dense or expert FFN.  Runs on the paged plane (and whole
        sequences without a cache); it keeps no slot-plane cache."""
        cfg = self.cfg
        h = rms_norm(x, bp["ln1"], cfg.norm_eps)
        q_c, q_r, c, k_r = attn.mla_project(bp["attn"], h, cfg,
                                            positions=positions)
        scale = attn.mla_scale(cfg)
        new_cache, valid = None, None
        if chunked:
            s = x.shape[1]
            valid = jnp.arange(s)[None, :] < lens[:, None]
            pages = attn.update_latent_pages(
                cache["latent_pages"], page_table, c, k_r, positions, valid)
            kv_len = positions[:, 0] + lens
            if s > 1:
                ctx = attn.paged_mla_chunk_attention(
                    q_c, q_r, pages, page_table, q_pos=positions,
                    kv_len=kv_len, chunk_lens=lens, scale=scale,
                )
            else:
                # the one kernel decision (see repro.kernels.ops)
                fn = (ops.paged_mla_decode_attention if ops.kernels_enabled()
                      else attn.paged_mla_decode_attention)
                ctx = fn(q_c[:, :, 0], q_r[:, :, 0], pages, page_table,
                         kv_len, scale=scale)[:, :, None]
            new_cache = {"latent_pages": pages}
        elif decode or make_cache:
            raise NotImplementedError(
                "latent attention keeps its cache in the paged plane "
                "only; serve it through chunk_step")
        else:
            ctx = attn.mla_attention(q_c, q_r, c, k_r, lens=lens,
                                     scale=scale)
        x, aux = self._ffn_block(bp, x + attn.mla_output(bp["attn"], ctx),
                                 kind, valid)
        return x, new_cache, aux

    def _build_cache(self, k, v, lens, window, cache_len):
        if window > 0:
            kc, vc, pos = attn.build_local_cache(k, v, lens, window)
            return {"k": kc, "v": vc, "pos": pos}
        b, h, s, hd = k.shape
        pos = jnp.where(
            jnp.arange(s)[None, :] < lens[:, None],
            jnp.arange(s)[None, :], -1
        )
        pos = jnp.broadcast_to(pos, (b, s))
        if cache_len > s:
            padw = cache_len - s
            k = jnp.pad(k, ((0, 0), (0, 0), (0, padw), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, padw), (0, 0)))
            pos = jnp.pad(pos, ((0, 0), (0, padw)), constant_values=-1)
        return {"k": k, "v": v, "pos": pos}

    def _mamba_block(self, bp, x, *, cache, make_cache, decode,
                     lens=None):
        cfg = self.cfg
        h = rms_norm(x, bp["ln"], cfg.norm_eps)
        conv_state = cache["conv"] if cache is not None else None
        ssm_state = cache["ssm"] if cache is not None else None
        y, (new_conv, new_ssm) = mamba2.mamba_block(
            bp["mamba"], h, cfg, conv_state=conv_state, ssm_state=ssm_state,
            decode=decode, unroll=self.unroll, lens=lens if make_cache else None,
        )
        new_cache = None
        if make_cache or decode:
            new_cache = {"conv": new_conv, "ssm": new_ssm}
        out = constrain(x + y, "batch", "seq", "residual")
        return out, new_cache, _no_aux()

    def _block(self, kind, bp, shared, x, *, positions, lens, cache,
               make_cache, cache_len, decode, chunked=False,
               page_table=None):
        if kind == "mamba":
            return self._mamba_block(
                bp, x, cache=cache, make_cache=make_cache, decode=decode,
                lens=lens,
            )
        if kind == "shared_attn":
            bp = shared
            kind = "dense"
        return self._attn_block(
            bp, x, kind, positions=positions, lens=lens, cache=cache,
            make_cache=make_cache, cache_len=cache_len, decode=decode,
            chunked=chunked, page_table=page_table,
        )

    # -- segment runners ------------------------------------------------------
    def _run_uniform(self, spec, seg_params, shared, x, *, positions, lens,
                     cache, make_cache, cache_len, decode, chunked=False,
                     page_table=None):
        if spec.kind == "shared_attn":
            x, new_cache, aux = self._block(
                "shared_attn", None, shared, x, positions=positions,
                lens=lens, cache=cache, make_cache=make_cache,
                cache_len=cache_len, decode=decode, chunked=chunked,
                page_table=page_table,
            )
            return x, new_cache, aux

        def layer(carry, xs):
            bp = xs[0]
            c = xs[1] if len(xs) > 1 else None
            y, new_c, aux = self._block(
                spec.kind, bp, shared, carry, positions=positions, lens=lens,
                cache=c, make_cache=make_cache, cache_len=cache_len,
                decode=decode, chunked=chunked, page_table=page_table,
            )
            outs = (aux,) if new_c is None else (aux, new_c)
            return y, outs

        if self.remat:
            layer = jax.checkpoint(
                layer, policy=jax.checkpoint_policies.nothing_saveable
            )
        xs = (seg_params,) if cache is None else (seg_params, cache)
        if self.unroll:
            outs_list = []
            for i in range(spec.count):
                xs_i = jax.tree.map(lambda a: a[i], xs)
                x, outs_i = layer(x, xs_i)
                outs_list.append(outs_i)
            aux = jnp.sum(jnp.stack([o[0] for o in outs_list]), axis=0)
            if len(outs_list[0]) > 1:
                new_cache = jax.tree.map(
                    lambda *leaves: jnp.stack(leaves),
                    *[o[1] for o in outs_list],
                )
            else:
                new_cache = None
            return x, new_cache, aux
        x, outs = jax.lax.scan(layer, x, xs)
        aux = jnp.sum(outs[0], axis=0)
        new_cache = outs[1] if len(outs) > 1 else None
        return x, new_cache, aux

    def _run_group(self, spec, seg_params, shared, x, *, positions, lens,
                   cache, make_cache, cache_len, decode, chunked=False,
                   page_table=None):
        inner = spec.inner

        def group_body(carry, xs):
            gp, gcache = xs
            y = carry
            auxes = []
            new_caches = {}
            for ikind, icount in inner:
                sub_spec = SegSpec(ikind, icount)
                sub_params = None if ikind == "shared_attn" else gp[ikind]
                sub_cache = None if gcache is None else gcache.get(ikind)
                y, nc, aux = self._run_uniform(
                    sub_spec, sub_params, shared, y, positions=positions,
                    lens=lens, cache=sub_cache, make_cache=make_cache,
                    cache_len=cache_len, decode=decode, chunked=chunked,
                    page_table=page_table,
                )
                auxes.append(aux)
                if nc is not None:
                    new_caches[ikind] = nc
            outs = (sum(auxes),)
            if new_caches:
                outs = outs + (new_caches,)
            return y, outs

        if self.unroll:
            outs_list = []
            for i in range(spec.count):
                gp_i = jax.tree.map(lambda a: a[i], seg_params)
                gc_i = (None if cache is None
                        else jax.tree.map(lambda a: a[i], cache))
                x, outs_i = group_body(x, (gp_i, gc_i))
                outs_list.append(outs_i)
            aux = jnp.sum(jnp.stack([o[0] for o in outs_list]), axis=0)
            if len(outs_list[0]) > 1:
                new_cache = jax.tree.map(
                    lambda *leaves: jnp.stack(leaves),
                    *[o[1] for o in outs_list],
                )
            else:
                new_cache = None
            return x, new_cache, aux
        if cache is None:
            def body_nc(carry, gp):
                return group_body(carry, (gp, None))
            x, outs = jax.lax.scan(body_nc, x, seg_params)
        else:
            x, outs = jax.lax.scan(group_body, x, (seg_params, cache))
        aux = jnp.sum(outs[0], axis=0)
        new_cache = outs[1] if len(outs) > 1 else None
        return x, new_cache, aux

    def _run_segments(self, params, x, *, positions, lens, caches,
                      make_cache, cache_len, decode, chunked=False,
                      page_table=None):
        shared = params.get("shared")
        new_caches = []
        aux_total = _no_aux()
        for i, spec in enumerate(self.segments):
            seg_p = params["segments"][i]
            seg_c = caches[i] if caches is not None else None
            runner = self._run_group if spec.kind == "group" else (
                self._run_uniform
            )
            x, nc, aux = runner(
                spec, seg_p, shared, x, positions=positions, lens=lens,
                cache=seg_c, make_cache=make_cache, cache_len=cache_len,
                decode=decode, chunked=chunked, page_table=page_table,
            )
            new_caches.append(nc)
            aux_total = aux_total + aux
        return x, new_caches, aux_total

    # -- public API -----------------------------------------------------------
    def _embed_in(self, params, batch):
        cfg = self.cfg
        if cfg.frontend == "frames":
            x = batch["frames"].astype(self.compute_dtype)
        else:
            x = embed(batch["tokens"], params["embed"], self.compute_dtype)
        return x

    def _head(self, params, x):
        """Vocabulary logits of hidden states ``x``: the tied embedding
        or the separate head."""
        table = (params["embed"] if self.cfg.tie_embeddings
                 else params["head"])
        return x @ table.T.astype(x.dtype)

    @_at_precision
    def forward(self, params, batch, return_aux: bool = False):
        """Full-sequence forward -> logits (B, S, V)."""
        cfg = self.cfg
        x = self._embed_in(params, batch)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        lens = batch.get("lens", jnp.full((b,), s, jnp.int32))
        x, _, aux = self._run_segments(
            params, x, positions=positions, lens=lens, caches=None,
            make_cache=False, cache_len=s, decode=False,
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = constrain(self._head(params, x), "batch", "seq", "vocab")
        if return_aux:
            return logits, aux
        return logits

    def loss(self, params, batch):
        """Next-token (or masked-prediction) CE + MoE balance aux."""
        logits, aux = self.forward(params, batch, return_aux=True)
        labels = batch["labels"]
        mask = batch.get("mask")
        logits32 = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits32, axis=-1)
        ll = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
        tok_loss = lse - ll
        if mask is not None:
            mask = mask.astype(jnp.float32)
            ce = jnp.sum(tok_loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        else:
            ce = jnp.mean(tok_loss)
        return ce + 0.01 * aux[0]

    @_at_precision
    def prefill(self, params, tokens_or_frames, lens, *,
                cache_len: Optional[int] = None):
        """Process prompts, return (last-token logits (B, V), caches)."""
        cfg = self.cfg
        if cfg.frontend == "frames":
            batch = {"frames": tokens_or_frames}
        else:
            batch = {"tokens": tokens_or_frames}
        x = self._embed_in(params, batch)
        b, s = x.shape[:2]
        cache_len = cache_len or s
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x, caches, _ = self._run_segments(
            params, x, positions=positions, lens=lens, caches=None,
            make_cache=not cfg.is_encoder_only, cache_len=cache_len,
            decode=False,
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return self._head(params, _at_last(x, lens)), caches

    @property
    def supports_chunked(self) -> bool:
        """Chunked prefill over paged caches handles every block kind
        except sliding-window rings (bounded anyway) and encoder-only /
        frame-frontend models (never served incrementally)."""
        if self.cfg.is_encoder_only or self.cfg.frontend == "frames":
            return False
        return self.block_kinds <= {"dense", "moe", "mamba", "global",
                                    "shared_attn", "mla", "mla_dense"}

    @property
    def supports_prefix_cache(self) -> bool:
        """Page-level prefix reuse is exact only when ALL per-token
        state lives in paged K/V.  Mamba blocks keep SSM/conv state
        slot-resident (see ``init_paged_cache``), so a shared page
        cannot reproduce the recurrent state the skipped prefill would
        have produced — prefix caching must refuse such models."""
        if not self.supports_chunked:
            return False
        return "mamba" not in self.block_kinds

    @property
    def supports_spec_decode(self) -> bool:
        """Speculative rollback is page-table truncation, which can
        only restore state that lives in paged K/V.  Mamba/hybrid
        blocks mutate slot-resident SSM/conv state sequentially with
        no per-position record to truncate back to, so speculation is
        refused for them — mirroring ``supports_prefix_cache``."""
        return self.supports_prefix_cache

    def _paged_forward(self, params, caches, page_table, tokens, start,
                       lens):
        """The paged plane's forward: embed ``tokens`` (B, C) at
        positions ``start + i``, run every segment over the paged
        caches with ``lens`` (B,) valid tokens per row (0 freezes a
        row: writes dropped, SSM state held), final norm.  Returns
        (hidden states (B, C, D), new caches, aux)."""
        x = embed(tokens, params["embed"], self.compute_dtype)
        positions = start[:, None] + jnp.arange(tokens.shape[1])[None, :]
        x, new_caches, aux = self._run_segments(
            params, x, positions=positions, lens=lens, caches=caches,
            make_cache=True, cache_len=0, decode=False, chunked=True,
            page_table=page_table,
        )
        return (rms_norm(x, params["final_norm"], self.cfg.norm_eps),
                new_caches, aux)

    @_at_precision
    def chunk_step(self, params, caches, page_table, tokens, start,
                   chunk_lens):
        """One step over *paged* caches: a prefill chunk, or (C == 1)
        the body of a decode block.

        tokens: (B, C) right-padded chunk tokens; start: (B,) absolute
        position of each row's first token; chunk_lens: (B,) valid
        counts — 0 freezes a row, so idle slots ride along in the same
        jitted call.  page_table: (B, MP) int32.  Returns (logits
        (B, V) at each row's last valid token, new caches).
        """
        x, new_caches, aux = self._paged_forward(
            params, caches, page_table, tokens, start, chunk_lens)
        logits = self._head(params, _at_last(x, chunk_lens))
        return (logits, new_caches) + self._expert_load(aux)

    def _expert_load(self, aux) -> tuple:
        """``(expert_tokens, experts_hit)`` int32 as a one-item tuple
        for a model with an expert layer, else ()."""
        if not self.counts_experts:
            return ()
        return (aux[1:].astype(jnp.int32),)

    # -- decode blocks ---------------------------------------------------------
    #
    # Every decode dispatch runs K >= 1 greedy iterations in a
    # ``lax.scan`` whose carry holds the caches AND the generation
    # state (last token, position, alive mask, remaining-output
    # budget), so the host round-trip — dispatch, sync, download
    # tokens — is paid once per K tokens, and the state stays on the
    # device from one block to the next.  Stopping (EOS, max-len,
    # per-request l_out) is evaluated on device by ``_stops``: a row
    # that finishes mid-block freezes (its chunk length drops to 0, so
    # cache writes are dropped / become idempotent and its later lanes
    # are marked invalid).

    def _decode_block_body(self, last, pos, alive, rem, eos, max_len,
                           logits):
        """Shared post-logits state transition for both block planes."""
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        step = alive.astype(jnp.int32)
        tok = jnp.where(alive, nxt, last)      # frozen rows keep state
        new_pos = pos + step
        new_rem = rem - step
        new_alive = alive & ~_stops(tok, new_pos, new_rem, eos, max_len)
        return tok, new_pos, new_alive, new_rem

    def decode_block(self, params, caches, page_table, last, pos, alive,
                     rem, eos, max_len, *, k: int):
        """K fused greedy decode iterations over *paged* caches.

        last/pos/rem: (B,) int32 device state; alive: (B,) bool (False
        rows — idle or mid-prefill slots — are frozen: zero chunk
        length drops their writes); eos: int32 (-1 disables); max_len:
        int32; ``k`` is static (jit per block size).
        Returns ``(tokens (B, K), valid (B, K), last, pos), caches``
        (a model with an expert layer adds its load summed over the
        block after ``pos``) —
        ``valid[b, i]`` marks lanes that really emitted a token, so a
        row stopping mid-block yields a partially-consumed block.
        """
        def body(carry, _):
            caches, last, pos, alive, rem = carry
            logits, caches, *load = self.chunk_step(
                params, caches, page_table, last[:, None], pos,
                alive.astype(jnp.int32),
            )
            tok, new_pos, new_alive, new_rem = self._decode_block_body(
                last, pos, alive, rem, eos, max_len, logits,
            )
            return ((caches, tok, new_pos, new_alive, new_rem),
                    (tok, alive, *load))

        init = (caches, last, pos, alive, rem)
        (caches, last, pos, alive, rem), (toks, valid, *load) = (
            jax.lax.scan(body, init, None, length=k))
        # the held experts' load, summed over the block's iterations
        load = tuple(jnp.sum(a, axis=0) for a in load)
        return (toks.T, valid.T, last, pos) + load, caches

    def decode_block_slots(self, params, caches, last, pos, alive, rem,
                           eos, max_len, *, k: int):
        """Slot-plane (contiguous-row caches) twin of
        :meth:`decode_block`: same fused scan over ``decode_step``.

        The slot plane has no chunk-length freeze, so a finished row
        keeps re-running its *last* token at its *frozen* position —
        attention cache writes become idempotent overwrites and the
        row's lanes are marked invalid (its SSM state self-pollutes
        harmlessly: the engine clears the row at retire).
        """
        def body(carry, _):
            caches, last, pos, alive, rem = carry
            logits, caches = self.decode_step(params, caches, last, pos)
            tok, new_pos, new_alive, new_rem = self._decode_block_body(
                last, pos, alive, rem, eos, max_len, logits,
            )
            return (caches, tok, new_pos, new_alive, new_rem), (tok, alive)

        init = (caches, last, pos, alive, rem)
        (caches, last, pos, alive, rem), (toks, valid) = jax.lax.scan(
            body, init, None, length=k
        )
        return (toks.T, valid.T, last, pos), caches

    @_at_precision
    def spec_decode_block(self, params, caches, page_table, last, pos,
                          alive, rem, props, prop_lens, eos, max_len,
                          *, k: int):
        """One propose-verify-accept speculative dispatch over *paged*
        caches: score ``last`` plus up to ``k`` drafted tokens in a
        single forward pass, then accept the longest prefix of the
        proposal that greedy decode would have produced itself.

        props: (B, K) drafted continuations; prop_lens: (B,) valid
        draft counts (0 rides along as a plain 1-token decode).  Lane
        ``i`` of the verify chunk holds the token whose KV lands at
        position ``pos + i`` and whose logits greedily pick the token
        for position ``pos + i + 1`` — so ``t[:, i]`` is exactly what
        ``i`` plain decode steps would emit, as long as every earlier
        proposal matched.  The same on-device stopping predicate as
        :meth:`decode_block` runs per lane, so EOS / l_out / max_len
        cut the accepted span exactly where a decode block would
        stop.  Returns ``(tokens (B, K+1), valid (B, K+1), last, pos),
        caches``; ``valid`` is prefix-contiguous per row and the
        caller rolls rejected lanes' KV back by truncating the page
        table to the returned ``pos``.
        """
        b = last.shape[0]
        tokens = jnp.concatenate([last[:, None], props], axis=1)
        chunk_lens = jnp.where(alive, 1 + prop_lens, 0).astype(jnp.int32)
        x, new_caches, aux = self._paged_forward(
            params, caches, page_table, tokens, pos, chunk_lens)
        logits = self._head(params, x)                # (B, K+1, V)
        t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        idx = jnp.arange(k + 1)[None, :]
        # lane i+1 is reachable iff proposal i matched the greedy pick
        # of lane i (and was a real draft); lane 0 always is
        match = (props == t[:, :k]) & (jnp.arange(k)[None, :]
                                       < prop_lens[:, None])
        reach = jnp.concatenate(
            [jnp.ones((b, 1), bool),
             jnp.cumprod(match.astype(jnp.int32), axis=1).astype(bool)],
            axis=1,
        )
        # per-lane stopping, evaluated as if the lane's token had been
        # appended by a decode block
        done_i = _stops(t, pos[:, None] + idx + 1, rem[:, None] - (idx + 1),
                        eos, max_len)
        stopped_before = jnp.concatenate(
            [jnp.zeros((b, 1), bool),
             jnp.cumsum(done_i.astype(jnp.int32), axis=1)[:, :-1] > 0],
            axis=1,
        )
        valid = alive[:, None] & reach & ~stopped_before
        emitted = jnp.sum(valid.astype(jnp.int32), axis=1)
        new_pos = pos + emitted
        pick = jnp.clip(emitted - 1, 0, k)
        last_tok = jnp.take_along_axis(t, pick[:, None], axis=1)[:, 0]
        new_last = jnp.where(emitted > 0, last_tok, last)
        return ((t, valid, new_last, new_pos) + self._expert_load(aux),
                new_caches)

    @_at_precision
    def decode_step(self, params, caches, tokens, pos):
        """tokens: (B,) int32 last sampled; pos: (B,) their positions.

        Returns (logits (B, V), new caches).
        """
        cfg = self.cfg
        x = embed(tokens[:, None], params["embed"], self.compute_dtype)
        positions = pos[:, None]
        lens = pos + 1
        x, new_caches, _ = self._run_segments(
            params, x, positions=positions, lens=lens, caches=caches,
            make_cache=False, cache_len=0, decode=True,
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return self._head(params, x[:, 0]), new_caches

    # -- cache allocation (for the real serving engine & dry-run specs) -------
    def init_cache(self, batch_size: int, max_len: int):
        """Zero caches with static shapes (dtype = compute_dtype)."""
        cfg = self.cfg
        if self.block_kinds & set(MLA_KINDS):
            raise NotImplementedError(
                "latent attention keeps its cache in the paged plane only")
        hd = cfg.resolved_head_dim
        hkv = cfg.n_kv_heads * self.kv_repeat

        def attn_cache(n_lead, window):
            slen = min(window, max_len) if window else max_len
            shape = (batch_size, hkv, slen, hd)
            lead = tuple(n_lead)
            return {
                "k": jnp.zeros(lead + shape, self.compute_dtype),
                "v": jnp.zeros(lead + shape, self.compute_dtype),
                "pos": jnp.full(lead + (batch_size, slen), -1, jnp.int32),
            }

        mamba_cache = partial(self._mamba_cache, batch_size)

        def seg_cache(spec: SegSpec, lead=()):
            if spec.kind == "group":
                return {
                    ikind: seg_cache(
                        SegSpec(ikind, icount), lead + (spec.count,)
                    )
                    for ikind, icount in spec.inner
                }
            if spec.kind == "mamba":
                return mamba_cache(lead + (spec.count,))
            if spec.kind == "shared_attn":
                return attn_cache(lead, 0)
            window = cfg.window if spec.kind == "local" else 0
            return attn_cache(lead + (spec.count,), window)

        return [seg_cache(s) for s in self.segments]

    def _mamba_cache(self, batch_size: int, n_lead):
        cfg = self.cfg
        di, h, n, g, p, cw = mamba2.mamba_dims(cfg)
        lead = tuple(n_lead)
        return {
            "conv": {
                "x": jnp.zeros(
                    lead + (batch_size, cw - 1, di), self.compute_dtype
                ),
                "bc": jnp.zeros(
                    lead + (batch_size, cw - 1, 2 * g * n),
                    self.compute_dtype,
                ),
            },
            "ssm": jnp.zeros(
                lead + (batch_size, h, p, n), jnp.float32
            ),
        }

    def init_paged_cache(self, n_slots: int, max_len: int,
                         page_size: int, n_pages: Optional[int] = None):
        """Paged-plane caches: attention K/V live in a shared pool of
        `n_pages` fixed-size pages (indexed through the engine's page
        table); O(1)-per-sequence SSM/conv state stays slot-indexed.
        """
        assert self.supports_chunked, (
            "paged caches need chunk-capable segments (no local windows "
            "/ encoder frontends); use init_cache"
        )
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        hkv = cfg.n_kv_heads * self.kv_repeat
        if n_pages is None:
            n_pages = n_slots * (-(-max_len // page_size))

        def paged_attn(n_lead, kind):
            lead = tuple(n_lead)
            if kind in MLA_KINDS:
                # one [latent, rotary key] row per token, laid out as a
                # one-head pool
                shape = (n_pages, 1, page_size, cfg.mla.pool_width)
                return {"latent_pages": jnp.zeros(lead + shape,
                                                  self.compute_dtype)}
            shape = (n_pages, hkv, page_size, hd)
            return {
                "k_pages": jnp.zeros(lead + shape, self.compute_dtype),
                "v_pages": jnp.zeros(lead + shape, self.compute_dtype),
            }

        def seg_cache(spec: SegSpec, lead=()):
            if spec.kind == "group":
                return {
                    ikind: seg_cache(
                        SegSpec(ikind, icount), lead + (spec.count,)
                    )
                    for ikind, icount in spec.inner
                }
            if spec.kind == "mamba":
                return self._mamba_cache(n_slots, lead + (spec.count,))
            if spec.kind == "shared_attn":
                return paged_attn(lead, "dense")
            return paged_attn(lead + (spec.count,), spec.kind)

        return [seg_cache(s) for s in self.segments]

    def paged_cache_axes(self):
        """Batch-axis pytree for init_paged_cache (matches cache_axes
        semantics); paged K/V pools get None — they are reclaimed by
        the page allocator, never by row surgery."""
        def seg_axes(spec: SegSpec, lead=()):
            if spec.kind == "group":
                return {
                    ikind: seg_axes(SegSpec(ikind, icount),
                                    lead + (spec.count,))
                    for ikind, icount in spec.inner
                }
            if spec.kind == "mamba":
                b = len(lead) + 1
                return {"conv": {"x": b, "bc": b}, "ssm": b}
            if spec.kind in MLA_KINDS:
                return {"latent_pages": None}
            return {"k_pages": None, "v_pages": None}

        return [seg_axes(s) for s in self.segments]

    def cache_logical_axes(self):
        """Pytree (same structure as init_cache) of logical-axis tuples,
        for building NamedShardings of decode caches in the launcher."""
        def attn_axes(n_lead):
            lead = ("layers",) * len(n_lead)
            return {
                "k": lead + ("batch", "kv_heads", "cache_seq", None),
                "v": lead + ("batch", "kv_heads", "cache_seq", None),
                "pos": lead + ("batch", "cache_seq"),
            }

        def mamba_axes(n_lead):
            lead = ("layers",) * len(n_lead)
            return {
                "conv": {
                    "x": lead + ("batch", None, "ssm_inner"),
                    "bc": lead + ("batch", None, None),
                },
                "ssm": lead + ("batch", "ssm_heads", None, None),
            }

        def seg_axes(spec: SegSpec, lead=()):
            if spec.kind == "group":
                return {
                    ikind: seg_axes(SegSpec(ikind, icount),
                                    lead + (spec.count,))
                    for ikind, icount in spec.inner
                }
            if spec.kind == "mamba":
                return mamba_axes(lead + (spec.count,))
            if spec.kind == "shared_attn":
                return attn_axes(lead)
            return attn_axes(lead + (spec.count,))

        return [seg_axes(s) for s in self.segments]

    def cache_axes(self):
        """Pytree (same structure as init_cache) of batch-axis indices.

        Lets the serving engine insert/extract per-sequence cache rows
        without hard-coding each leaf's layout.
        """
        def attn_axes(n_lead):
            b = len(n_lead)
            return {"k": b, "v": b, "pos": b}

        def mamba_axes(n_lead):
            b = len(n_lead)
            return {"conv": {"x": b, "bc": b}, "ssm": b}

        def seg_axes(spec: SegSpec, lead=()):
            if spec.kind == "group":
                return {
                    ikind: seg_axes(SegSpec(ikind, icount),
                                    lead + (spec.count,))
                    for ikind, icount in spec.inner
                }
            if spec.kind == "mamba":
                return mamba_axes(lead + (spec.count,))
            if spec.kind == "shared_attn":
                return attn_axes(lead)
            return attn_axes(lead + (spec.count,))

        return [seg_axes(s) for s in self.segments]


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)
