"""Train/serve step factories.

`make_train_step` builds the jitted SPMD step: microbatch gradient
accumulation via `lax.scan` (lets XLA overlap each microbatch's gradient
reduce-scatter with the next microbatch's compute), remat inside the model's
superblock scan, AdamW on fp32 masters.

`make_serve_step` / `make_prefill_step` build the decode-path steps lowered
by the `decode_*` / `long_*` dry-run cells.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ArchConfig
from repro.models import model as M
from .optimizer import AdamW
from .train_state import TrainState


def make_train_step(cfg: ArchConfig, opt: AdamW, accum_steps: int = 1,
                    aux_weight: float = 0.01):
    """Returns train_step(state, batch) → (state, metrics).

    batch: {"tokens": (B, T) i32, "labels": (B, T) i32,
            optional "frontend": (B, S, d) f32}.
    With accum_steps > 1, B must divide evenly; gradients are accumulated
    over accum_steps microbatches in fp32.
    """

    def loss_fn(params, mb):
        loss, nll = M.lm_loss(params, cfg, mb["tokens"], mb["labels"],
                              frontend_embeds=mb.get("frontend"),
                              aux_weight=aux_weight)
        return loss, nll

    @jax.named_scope("train_step")
    def train_step(state: TrainState, batch):
        params = state.params

        if accum_steps == 1:
            (loss, nll), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            def split(x):
                # strided µbatches: row j of µbatch i is global row j·A + i,
                # so each µbatch stays sharded across the FULL data axis
                # (a contiguous reshape would split the DP axis between the
                # scan dim and the batch dim — 8× the live activation set).
                mb = x.shape[0] // accum_steps
                return x.reshape(mb, accum_steps, *x.shape[1:]).swapaxes(0, 1)
            mbs = {k: split(v) for k, v in batch.items()}

            def accum(carry, mb):
                g_acc, l_acc, n_acc = carry
                (l, n), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l, n_acc + n), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss, nll), _ = lax.scan(
                accum, (g0, jnp.zeros(()), jnp.zeros(())), mbs)
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss, nll = loss / accum_steps, nll / accum_steps

        new_params, opt_state, om = opt.update(grads, state.opt_state, params)
        metrics = {"loss": loss, "nll": nll, **om}
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=opt_state), metrics

    return train_step


def make_prefill_step(cfg: ArchConfig, prefix_len: int = 0):
    """prefill(params, tokens, cache, [frontend]) → (logits_last, cache).

    With `prefix_len > 0` (continued prefill — the serve engine's
    prefix-cache hits), `tokens` holds only a prompt's uncached suffix and
    the cache's first `prefix_len` rows are pre-loaded shared-prefix KV;
    rope positions, the cache write offset, and the attention masks all
    start at `prefix_len` (model.forward / layers.attention_block)."""

    def prefill(params, tokens, cache, frontend=None):
        logits, cache, _ = M.forward(params, cfg, tokens, cache=cache,
                                     frontend_embeds=frontend,
                                     last_only=True, prefix_len=prefix_len)
        return logits, cache

    return prefill


def make_bucketed_prefill_step(cfg: ArchConfig, prefix_len: int = 0):
    """Bucketed prefill for the serve engine's prompt-length bucketing
    (DESIGN.md §10 satellite): `tokens` is a suffix right-padded up to a
    bucket length, so mixed prompt lengths share one jit trace per
    (prefix_len, bucket) instead of retracing per distinct length.

    prefill(params, tokens, cache, last_idx, valid_len) →
    (logits_last, cache):

    * `last_idx` (traced) is the real suffix's last row — the lm_head runs
      on that row, not the padded block's end (model.forward last_index);
    * `valid_len` (traced) is the real ABSOLUTE prompt length: every cache
      row at position >= valid_len had its K/V computed from padding, so
      its position is forced to -1 after the forward — invisible to the
      attention mask (layers.decode_attention masks kv_positions >= 0),
      exactly like an empty ring entry, and overwritten in place once the
      request decodes past it. Real rows never see the padded ones
      (causal masking), so their K/V and the selected logits row come out
      of the same arithmetic as an exact-length prefill;
    * recurrent layers (ssm, hybrid, granite-4.0-h's Mamba2 layers) read
      `valid_len` too: padded rows get dt = 0, which leaves the SSM state
      as it stands at the real length (decay 1, no input), and the conv
      tail is the last inputs before it (models/ssm.mamba2_block).

    Local-window ring writes past the real length could wrap onto live
    rows, so the engine gates bucketing off for local-window stacks
    (ServeEngine.bucketing_on)."""
    from repro.models.layers import KVCache

    def prefill(params, tokens, cache, last_idx, valid_len, frontend=None):
        logits, cache, _ = M.forward(params, cfg, tokens, cache=cache,
                                     frontend_embeds=frontend,
                                     last_only=True, last_index=last_idx,
                                     prefix_len=prefix_len,
                                     valid_len=valid_len)

        def mask(leaf):
            if not isinstance(leaf, KVCache):
                return leaf
            S = leaf.positions.shape[-1]
            keep = jnp.arange(S, dtype=jnp.int32) < valid_len
            return leaf._replace(
                positions=jnp.where(keep, leaf.positions, -1))

        cache = jax.tree.map(mask, cache,
                             is_leaf=lambda x: isinstance(x, KVCache))
        return logits, cache

    return prefill


def make_serve_step(cfg: ArchConfig):
    """One decode step: (params, token (B,1), cache, pos (B,), [frontend]) →
    (logits (B,1,V), new_cache). The `decode_*`/`long_*` dry-run target.

    `pos` is a per-slot position vector — under continuous batching each
    batch row serves an independent request at its own depth (scalars are
    broadcast for single-sequence callers)."""

    def serve_step(params, token, cache, pos, frontend=None):
        logits, cache, _ = M.forward(params, cfg, token, cache=cache,
                                     pos=pos, frontend_embeds=frontend)
        return logits, cache

    return serve_step


def make_draft_step(cfg: ArchConfig, draft_layers: int):
    """Early-exit decode step for self-speculative drafting (DESIGN.md §9):
    run only the first `draft_layers` superblocks of the *same* params —
    no second model — then the shared final norm + lm_head.

    (params, token (B,1), cache, pos (B,)) → (logits (B,1,V), new_cache).
    The forward runs the first `draft_layers` superblocks over the
    full-depth cache tree and writes their KV in place: consecutive draft
    steps must see each other's keys, and the verify forward later
    overwrites every position the draft wrote (all layers, pos..pos+k ⊇
    early layers, pos..pos+k-1), so a rejected draft leaves no live state
    behind.
    """
    E = draft_layers

    def draft_step(params, token, cache, pos):
        p = dict(params)
        p["layers"] = jax.tree.map(lambda x: x[:E], params["layers"])
        logits, cache, _ = M.forward(p, cfg, token, cache=cache, pos=pos)
        return logits, cache

    return draft_step


def make_verify_step(cfg: ArchConfig):
    """Batched speculative verify: (params, tokens (B, k+1), cache,
    pos (B,)) → (logits (B, k+1, V), new_cache). Column 0 is each slot's
    last emitted token, columns 1..k the draft; one full-depth forward in
    decode_multi mode scores all k+1 next-token distributions while
    writing KV at pos..pos+k per slot."""

    def verify_step(params, tokens, cache, pos):
        logits, cache, _ = M.forward(params, cfg, tokens, cache=cache,
                                     pos=pos, decode_multi=True)
        return logits, cache

    return verify_step
