"""Model: config -> params / loss_fn / prefill / decode_step.

One class serves all ten assigned architectures: the block pattern,
MoE/recurrent/enc-dec structure, and modality stubs all come from
``ArchConfig``.  Everything is pure functions over explicit param pytrees.

Batch conventions
-----------------
tokens mode   : {"tokens": (B,S) i32, "labels": (B,S) i32}
embeddings    : {"embeds": (B,S,d) bf16, "labels": (B,S) i32,
(vlm stub)       "positions": (B,S,3) i32 (M-RoPE)}
enc-dec       : {"enc_embeds": (B,Se,d) bf16, "tokens": (B,Sd) i32,
(audio stub)     "labels": (B,Sd) i32}
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import params as P
from repro.models.attention import select_attention
from repro.models.layers import (apply_norm, embed_specs, embed_tokens,
                                 head_matrix, norm_specs)
from repro.models.losses import chunked_softmax_xent
from repro.models.transformer import (BlockCtx, apply_stack,
                                      init_stack_cache, make_plan,
                                      stack_specs_tree)


class Model:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.plan = make_plan(cfg, cross=cfg.is_encdec)
        self.enc_plan = (make_plan(cfg, n_layers=cfg.n_enc_layers)
                         if cfg.is_encdec else None)

    # ----- parameters ----------------------------------------------------
    def param_specs(self):
        cfg = self.cfg
        specs = {"decoder": stack_specs_tree(cfg, self.plan),
                 "final_norm": norm_specs(cfg)}
        if cfg.input_mode == "tokens" or cfg.is_encdec:
            specs["embed"] = embed_specs(cfg)
        else:
            # modality stub: inputs are precomputed embeddings; only an
            # (untied) LM head is needed
            specs["embed"] = {
                "head": embed_specs(cfg)["head"]} if not cfg.tie_embeddings \
                else embed_specs(cfg)
        if cfg.is_encdec:
            specs["encoder"] = stack_specs_tree(cfg, self.enc_plan)
            specs["enc_final_norm"] = norm_specs(cfg)
        return specs

    def init(self, key):
        return P.materialize(self.param_specs(), key)

    def abstract_params(self):
        return P.abstract(self.param_specs())

    def param_axes(self):
        return P.axes_tree(self.param_specs())

    def n_params(self) -> int:
        return P.n_params(self.param_specs())

    # ----- forward -------------------------------------------------------
    def _positions(self, b, s, offset=0):
        pos = offset + jnp.arange(s)[None, :].astype(jnp.int32)
        pos = jnp.broadcast_to(pos, (b, s))
        if self.cfg.pos == "mrope":
            return jnp.broadcast_to(pos[..., None], (b, s, 3))
        return pos

    def _inputs(self, params, batch):
        cfg = self.cfg
        if cfg.is_encdec or cfg.input_mode == "tokens":
            x = embed_tokens(params["embed"], batch["tokens"], cfg)
        else:
            x = batch["embeds"].astype(cfg.compute_dtype)
        b, s = x.shape[:2]
        pos = batch.get("positions")
        if pos is None:
            pos = self._positions(b, s)
        return x, pos

    def _encode(self, params, batch, attn_len=None):
        cfg = self.cfg
        enc_x = batch["enc_embeds"].astype(cfg.compute_dtype)
        b, se = enc_x.shape[:2]
        ctx = BlockCtx(cfg=cfg, mode="train",
                       positions=self._positions(b, se),
                       attn_fn=select_attention(cfg, se), causal=False)
        h, _, _ = apply_stack(params["encoder"], enc_x, cfg, self.enc_plan,
                              ctx)
        return apply_norm(params["enc_final_norm"], h, cfg.norm)

    def forward(self, params, batch, *, mode="train", cache=None,
                shard_fn=lambda a, *n: a, remat=True,
                skip_future=False, use_ragged_kernel=False,
                decode_write_mask=None):
        """-> (hidden (B,S,d), new_cache, aux_loss)."""
        cfg = self.cfg
        x, pos = self._inputs(params, batch)
        b, s = x.shape[:2]
        enc_out = None
        if cfg.is_encdec and mode != "decode":
            enc_out = self._encode(params, batch)
        ctx = BlockCtx(cfg=cfg, mode=mode, positions=pos,
                       attn_fn=select_attention(
                           cfg, s,
                           skip_future=skip_future and mode == "prefill"),
                       causal=True,
                       enc_out=enc_out, shard_fn=shard_fn,
                       decode_idx=(cache or {}).get("idx"),
                       window_cache=(cfg.attn_window > 0
                                     and cfg.sub_quadratic),
                       ragged_kernel=use_ragged_kernel and mode == "decode",
                       decode_write_mask=(decode_write_mask
                                          if mode == "decode" else None),
                       page_table=((cache or {}).get("pt")
                                   if mode == "decode" else None))
        stack_cache = None if cache is None else cache["stack"]
        h, new_stack, aux = apply_stack(params["decoder"], x, cfg, self.plan,
                                        ctx, cache=stack_cache, remat=remat)
        h = apply_norm(params["final_norm"], h, cfg.norm)
        new_cache = None
        if cache is not None:
            idx = cache["idx"] + (1 if mode == "decode" else s)
            new_cache = {"stack": new_stack, "idx": idx}
            if "pt" in cache:
                # the page table is engine-owned and constant through a
                # traced step; it rides the cache pytree unchanged
                new_cache["pt"] = cache["pt"]
        return h, new_cache, aux

    # ----- training ------------------------------------------------------
    def loss_fn(self, params, batch, shard_fn=lambda a, *n: a,
                remat: bool = True, cast_params_once: bool = False):
        cfg = self.cfg
        if cast_params_once:
            # cast fp32 master weights to the compute dtype on their OWN
            # shards, so FSDP all-gathers move bf16 instead of fp32
            # (§Perf iteration; halves parameter-gather collective bytes)
            dt = jnp.dtype(cfg.compute_dtype)
            params = jax.tree.map(
                lambda p: p.astype(dt)
                if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)
        h, _, aux = self.forward(params, batch, mode="train",
                                 shard_fn=shard_fn, remat=remat)
        head = head_matrix(params["embed"], cfg)
        mask = batch.get("loss_mask")
        nll, n_tok = chunked_softmax_xent(h, head, batch["labels"],
                                          mask=mask)
        loss = nll
        metrics = {"nll": nll, "n_tokens": n_tok}
        if cfg.moe is not None:
            loss = loss + cfg.moe.aux_loss_coef * aux
            metrics["moe_aux"] = aux
        metrics["loss"] = loss
        return loss, metrics

    # ----- serving -------------------------------------------------------
    @property
    def supports_padded_prefill(self) -> bool:
        """True when trailing-pad bucketed prefill is exact: every block
        is attention (causal masking makes padding invisible to earlier
        positions) and no rolling-window cache (whose prefill keeps the
        LAST ``window`` positions, which padding would pollute).
        Recurrent blocks (rglru/mlstm/slstm) scan through pad tokens and
        corrupt their state, so they prefill at exact length."""
        from repro.models.transformer import ATTN_KINDS
        cfg = self.cfg
        descs = tuple(self.plan.prefix) + tuple(self.plan.period)
        return (all(d.kind in ATTN_KINDS for d in descs)
                and not (cfg.attn_window > 0 and cfg.sub_quadratic))

    @property
    def supports_paged_cache(self) -> bool:
        """True when the paged KV layout (DESIGN.md §13) is exact for
        this arch: every block full-context attention.  Rolling-window
        and recurrent blocks keep their own cache shapes, and enc-dec
        carries cross caches — all fall back to the contiguous layout
        (the engine checks this and silently disables paging)."""
        from repro.models.transformer import ATTN_KINDS
        cfg = self.cfg
        descs = tuple(self.plan.prefix) + tuple(self.plan.period)
        return (all(d.kind in ATTN_KINDS for d in descs)
                and cfg.attn_window == 0 and not cfg.is_encdec)

    def init_cache(self, batch_size: int, max_len: int,
                   enc_len: int = 0, per_slot: bool = False,
                   page_size: int = 0, n_pages: int = 0):
        """``per_slot`` makes ``idx`` a (B,) vector so every batch row
        decodes at its own position (continuous batching — ragged slot
        lengths in one shared cache).

        ``page_size > 0`` builds the PAGED cache: attention k/v become
        ``(n_pages, page_size, width)`` shared physical pages and the
        cache carries a sentinel-filled per-slot page table ``pt`` of
        shape ``(B, max_len // page_size)`` (sentinel = ``n_pages``).
        Requires ``supports_paged_cache``."""
        cfg = self.cfg
        if page_size > 0:
            assert self.supports_paged_cache, \
                f"{cfg.name}: arch does not support the paged KV cache"
            assert max_len % page_size == 0 and n_pages > 0, \
                (max_len, page_size, n_pages)
        stack = init_stack_cache(
            cfg, self.plan, batch_size, max_len, enc_len=enc_len,
            window_cache=(cfg.attn_window > 0 and cfg.sub_quadratic),
            page_size=page_size, n_pages=n_pages)
        idx = jnp.zeros((batch_size,) if per_slot else (), jnp.int32)
        cache = {"stack": stack, "idx": idx}
        if page_size > 0:
            cache["pt"] = jnp.full((batch_size, max_len // page_size),
                                   n_pages, jnp.int32)
        return cache

    def prefill(self, params, batch, cache, shard_fn=lambda a, *n: a,
                skip_future: bool = True, last_index=None):
        """Run the prompt, fill the cache; -> (last_logits, cache).
        ``skip_future`` uses the triangular attention schedule (forward-
        only; 2.8x compute on 32k prompts, EXPERIMENTS §Perf).

        ``last_index`` ((B,) int32) gathers each row's logits at its own
        last REAL token instead of position -1 — the bucketed-prefill path
        pads ragged prompts up to a shared length bucket, and causal
        attention makes trailing padding invisible to position
        ``last_index[b]`` (bit-identical to an exact-length prefill)."""
        h, new_cache, _ = self.forward(params, batch, mode="prefill",
                                       cache=cache, shard_fn=shard_fn,
                                       remat=False, skip_future=skip_future)
        if last_index is None:
            last = h[:, -1, :]
        else:
            b = h.shape[0]
            last = h[jnp.arange(b), jnp.asarray(last_index, jnp.int32), :]
        return self._lm_head(params, last), new_cache

    @jax.named_scope("lm_head")
    def _lm_head(self, params, h):
        """(B, d) hidden -> (B, V) float32 logits."""
        head = head_matrix(params["embed"], self.cfg)
        return (h @ head.astype(h.dtype)).astype(jnp.float32)

    def decode_step(self, params, cache, tokens=None, embeds=None,
                    shard_fn=lambda a, *n: a, use_ragged_kernel=False,
                    write_mask=None):
        """One decode step.  tokens: (B,) i32 (or embeds (B,d)).
        -> (logits (B,V) fp32, new_cache).

        With a ``per_slot`` cache (``idx`` is (B,)), each row decodes at
        its own position: RoPE, the cache write, and the attention mask
        all follow ``idx[b]`` (continuous batching).

        ``use_ragged_kernel`` routes eligible per-slot decode attention
        (full-context layers, vector ``idx``) through the Pallas
        ``flash_decode_attention`` kernel — the TPU data path; interpret
        mode (bit-exact semantics) everywhere else.  Rolling-window layers
        keep the jnp path, which stays the oracle either way.

        ``write_mask`` ((B,) bool) gates attention cache writes per row:
        the fused decode horizon passes the live-slot mask so finished
        slots stop writing while the batch keeps stepping on device."""
        cfg = self.cfg
        idx = cache["idx"]
        if tokens is not None:
            batch = {"tokens": tokens[:, None]}
            b = tokens.shape[0]
        else:
            batch = {"embeds": embeds[:, None, :]}
            b = embeds.shape[0]
        if jnp.ndim(idx) == 1:          # per-slot positions
            pos = idx[:, None].astype(jnp.int32)
        else:
            pos = jnp.broadcast_to(idx[None, None], (b, 1)).astype(jnp.int32)
        if cfg.pos == "mrope":
            pos = jnp.broadcast_to(pos[..., None], (b, 1, 3))
        batch["positions"] = pos
        h, new_cache, _ = self.forward(params, batch, mode="decode",
                                       cache=cache, shard_fn=shard_fn,
                                       remat=False,
                                       use_ragged_kernel=use_ragged_kernel,
                                       decode_write_mask=write_mask)
        return self._lm_head(params, h[:, 0, :]), new_cache

    def decode_horizon(self, params, cache, state, *, horizon: int,
                       max_len: int, use_ragged_kernel=False):
        """``horizon`` fused decode steps per host sync (greedy sampling).

        The serving analogue of the paper's doorbell batching: instead of
        one blocking device->host round-trip per generated token
        (``jnp.argmax`` -> ``np.array`` -> per-slot host loop), argmax
        sampling, budget decrement, EOS detection, and the finished mask
        all run inside one on-device loop of up to ``horizon`` steps,
        and the host drains the whole token trace in a single transfer.

        ``state`` (all (B,)): ``tok`` i32 next token to feed,
        ``remaining`` i32 decode budget, ``finished`` bool,
        ``eos`` i32 / ``has_eos`` bool per-slot EOS ids.

        -> (new_cache, new_state, trace) where every ``trace`` leaf is
        (horizon, B): ``tok`` the token emitted at that step, ``live``
        whether it counts, ``bonus_tok``/``bonus`` the extra cache-budget-
        exhaustion token, ``retired`` whether the slot finished there.
        Step semantics mirror the per-step host loop exactly
        (``ContinuousEngine.step`` with horizon 1 is the oracle):
        finished slots keep riding in the batch but feed a frozen token
        and stop writing their cache rows (``write_mask``), and the loop
        EXITS EARLY once every slot is finished (a ``while_loop``, so a
        horizon never burns device steps on an all-drained pool; unvisited
        trace rows stay all-dead)."""
        assert self.cfg.input_mode == "tokens" and not self.cfg.is_encdec, \
            "the fused horizon decodes token models"
        eos, has_eos = state["eos"], state["has_eos"]
        b = state["tok"].shape[0]
        trace0 = {"tok": jnp.zeros((horizon, b), jnp.int32),
                  "live": jnp.zeros((horizon, b), bool),
                  "bonus_tok": jnp.zeros((horizon, b), jnp.int32),
                  "bonus": jnp.zeros((horizon, b), bool),
                  "retired": jnp.zeros((horizon, b), bool)}

        def cond(carry):
            s, _, _, _, finished, _ = carry
            return (s < horizon) & ~finished.all()

        def body(carry):
            s, cache, tok, remaining, finished, trace = carry
            live = ~finished
            logits, cache = self.decode_step(
                params, cache, tokens=tok, write_mask=live,
                use_ragged_kernel=use_ragged_kernel)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            rem = jnp.where(live, remaining - 1, remaining)
            fin_new = live & ((rem <= 0) | (has_eos & (nxt == eos)))
            # cache idx advanced by decode_step; a live slot that would
            # overrun the cache emits its lookahead token and retires
            bonus = live & ~fin_new & (cache["idx"] >= max_len - 1)
            finished = finished | fin_new | bonus
            out = {"tok": tok, "live": live, "bonus_tok": nxt,
                   "bonus": bonus, "retired": live & finished}
            trace = {k: v.at[s].set(out[k]) for k, v in trace.items()}
            return (s + 1, cache, jnp.where(live, nxt, tok), rem,
                    finished, trace)

        _, cache, tok, remaining, finished, trace = jax.lax.while_loop(
            cond, body, (jnp.zeros((), jnp.int32), cache, state["tok"],
                         state["remaining"], state["finished"], trace0))
        new_state = dict(state, tok=tok, remaining=remaining,
                         finished=finished)
        return cache, new_state, trace
