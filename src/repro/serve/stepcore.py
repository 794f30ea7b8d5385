"""Step core of the serving engine: the jitted prefill/decode/verify
drivers and their key streams.

``StepCore`` owns everything that traces: the prefill-chunk entry, the
decode entry (which is the ``[B, k + 1]`` *verify* entry when speculative
decoding is on), and the deterministic PRNG streams that feed router skew
and sampling.  It holds no scheduling state — the engine passes in the
batch vectors (tokens, positions, active mask, block table) and replica /
residency tables each call, so one ``StepCore`` serves the ``unified``,
``prefill``-only, and ``decode``-only engine roles unchanged.

Every jitted signature is fixed at construction; ``jit_counts()`` exposes
the per-entry cache sizes that ``report()["jit_entries"]`` asserts stay
at one entry across admissions, recycling, growth, and role handoffs.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.serve.sampling import sample_tokens


class StepCore:
    def __init__(self, model, ecfg, *, skew: bool,
                 moe_policy: Optional[str], layer_diags: bool):
        self.model = model
        self.ecfg = ecfg
        self.skew = skew
        self.sample = ecfg.temperature > 0
        self.spec = ecfg.speculative_k > 0
        self.moe_policy = moe_policy
        self.layer_diags = layer_diags

        self.base_key = jax.random.PRNGKey(ecfg.skew_seed)
        self.pf_key = jax.random.fold_in(self.base_key, 0)
        self.dec_key = jax.random.fold_in(self.base_key, 1)
        self.samp_rng = (np.random.default_rng(ecfg.skew_seed + 101)
                         if self.sample else None)

        # every jitted entry is a named function, so a trace's modules
        # read jit_decode_step, jit_verify_step and jit_prefill_chunk
        if ecfg.paged:
            if self.spec:
                # speculative verify IS the decode step: one [B, k+1]
                # multi-token forward returning logits at every window
                # position; acceptance/sampling run host-side
                def verify_step(p, t, c, pos, bt, k, a, rep, res):
                    return self._verify_core(p, t, c, pos, k, a, bt, rep,
                                             res)
                self.decode_fn = jax.jit(verify_step)
            else:
                def decode_step(p, t, c, pos, bt, k, a, rep, res):
                    return self._decode_core(p, t, c, pos, k, a, bt, rep,
                                             res)
                self.decode_fn = jax.jit(decode_step)
        else:
            def decode_step(p, t, c, pos, k, a, rep, res):
                return self._decode_core(p, t, c, pos, k, a, None, rep, res)
            self.decode_fn = jax.jit(decode_step)
        # replica ids ride along as a trailing traced arg so between-window
        # weight swaps never re-trace (None = no replica slots: an empty
        # pytree, same trace either way).  With fused_paged_attention the
        # prefill chunk ALSO runs the q-tiled Pallas kernel: the slab
        # scratch is viewed as contiguous per-row blocks inside
        # attention_block's continue_prefill branch (strict — an
        # inapplicable fused path raises at warmup instead of silently
        # gathering); fused_moe_gmm routes the chunk's Bc * C expert
        # tokens through the grouped-GEMM kernel.
        pf_fused_attn = True if ecfg.fused_paged_attention else None
        pf_fused_moe = True if ecfg.fused_moe_gmm else None

        def prefill_chunk(p, t, c, pos, last, key, rep):
            return model.prefill_chunk(
                p, t, c, pos, last, key, moe_replica_ids=rep,
                fused_attention=pf_fused_attn, fused_moe=pf_fused_moe)
        self.prefill_fn = jax.jit(prefill_chunk)

    # ------------------------------------------------------------------
    def next_key(self, stream_key, idx: int):
        if not (self.skew or self.sample):
            return None
        return jax.random.fold_in(stream_key, idx)

    def _decode_core(self, params, tok, pool, pos, key, active, bt, rep,
                     res=None):
        skew_key = samp_key = None
        if self.skew and self.sample:
            skew_key = jax.random.fold_in(key, 0)
            samp_key = jax.random.fold_in(key, 1)
        elif self.skew:
            skew_key = key
        elif self.sample:
            samp_key = key
        kw: Dict[str, Any] = {}
        if bt is not None:
            kw = dict(block_table=bt, block_size=self.ecfg.kv_block_size)
            if self.ecfg.fused_paged_attention:
                kw["fused_attention"] = True
        if self.ecfg.fused_moe_gmm:
            kw["fused_moe"] = True
        logits, pool, _, diags = self.model.decode_step(
            params, tok, pool, pos, skew_key=skew_key, active_mask=active,
            moe_policy=self.moe_policy, moe_replica_ids=rep,
            moe_residency_ids=res,
            moe_layer_diags=self.layer_diags, **kw)
        with jax.named_scope("sample"):
            nxt = sample_tokens(logits, samp_key,
                                temperature=self.ecfg.temperature,
                                top_k=self.ecfg.top_k,
                                top_p=self.ecfg.top_p)
        return nxt, pool, diags

    def _verify_core(self, params, toks, pool, pos, key, active, bt, rep,
                     res=None):
        """Speculative verify step: ``toks`` [B, k+1] (window position 0 =
        the committed last token, 1..k = drafts) -> logits [B, k+1, V] at
        every window position.  No in-jit sampling — greedy acceptance /
        rejection sampling run host-side on the returned logits (the key
        feeds router skew only, folded exactly like ``_decode_core``)."""
        skew_key = None
        if self.skew:
            skew_key = jax.random.fold_in(key, 0) if self.sample else key
        kw: Dict[str, Any] = dict(block_table=bt,
                                  block_size=self.ecfg.kv_block_size)
        if self.ecfg.fused_paged_attention:
            kw["fused_attention"] = True
        if self.ecfg.fused_moe_gmm:
            kw["fused_moe"] = True
        logits, pool, _, diags = self.model.decode_step(
            params, toks, pool, pos, skew_key=skew_key, active_mask=active,
            moe_policy=self.moe_policy, moe_replica_ids=rep,
            moe_residency_ids=res,
            moe_layer_diags=self.layer_diags, **kw)
        return logits, pool, diags

    # ------------------------------------------------------------------
    def jit_counts(self) -> Dict[str, int]:
        return {
            "prefill_chunk": self.prefill_fn._cache_size(),
            "decode": self.decode_fn._cache_size(),
        }
