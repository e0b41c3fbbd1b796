"""The one traffic generator: it reads a mix file (``traffic/<name>.json``)
and makes every round's batch on the host from ``--seed``.

Two kinds of mix:

* ``lm`` — token streams as the program's ``FederatedLMData`` draws them:
  each client has its own unigram, a Zipf law reweighted by a Dirichlet
  skew, and each next token either follows a planted bigram or is drawn
  fresh. All fresh draws of a round come from one inverse-CDF lookup, so
  a round costs a handful of vector operations per position, not a draw
  over the whole vocabulary per position.
* ``frames`` — normal frame embeddings in bfloat16 with uniform targets.

A round's batch has the program's mesh layout: leaves ``(K, clients *
batch, ...)``, client ``c`` owning rows ``c * batch`` to ``(c + 1) * batch``.
"""
from __future__ import annotations

import numpy as np


class Traffic:
    def __init__(self, mix: dict, cfg: dict, seed: int):
        self.mix, self.seed = mix, seed
        self.vocab = cfg["vocab_size"]
        self.d_model = cfg["d_model"]
        if mix["kind"] == "lm":
            rng = np.random.default_rng([seed, 0x5EED])
            v = self.vocab
            base = 1.0 / np.arange(1, v + 1) ** mix["zipf_exponent"]
            skew = rng.dirichlet([mix["dirichlet_alpha"]] * v,
                                 size=mix["clients"])
            dist = base[None, :] * (0.5 + skew * v * 0.5)
            self.cdf = np.cumsum(dist / dist.sum(1, keepdims=True), axis=1)
            self.cdf[:, -1] = 1.0
        elif mix["kind"] != "frames":
            raise ValueError(f"unknown traffic kind {mix['kind']!r}")

    def round_batch(self, r: int) -> dict:
        """The batch of round ``r``: the same for the same seed and round."""
        rng = np.random.default_rng([self.seed, r + 1])
        m = self.mix
        K, B, S, n = m["local_steps"], m["batch"], m["seq_len"], m["clients"]
        if m["kind"] == "frames":
            import ml_dtypes
            x = rng.standard_normal((K, n * B, S, self.d_model), np.float32)
            x *= m["frame_std"]
            # bfloat16 is the top half of a float32: truncate, no rounding
            emb = (x.view(np.uint32) >> 16).astype(np.uint16).view(
                ml_dtypes.bfloat16)
            labels = rng.integers(0, self.vocab, (K, n * B, S), np.int32)
            return {"embeddings": emb, "labels": labels}
        rows = []
        for c in range(n):
            u = rng.random((K * B, S + 1))
            rows.append(np.searchsorted(self.cdf[c], u, side="right"))
        fresh = np.minimum(np.stack(rows, 1), self.vocab - 1)   # (KB, n, S+1)
        fresh = fresh.reshape(K, B, n, S + 1).transpose(0, 2, 1, 3)
        fresh = fresh.reshape(K * n * B, S + 1).astype(np.int64)
        bg = m["bigram"]
        follow = rng.random((K * n * B, S)) < bg["follow_prob"]
        toks = np.empty_like(fresh)
        toks[:, 0] = fresh[:, 0]
        for t in range(S):
            nxt = (toks[:, t] * bg["mult"] + bg["add"]) % self.vocab
            toks[:, t + 1] = np.where(follow[:, t], nxt, fresh[:, t + 1])
        toks = toks.astype(np.int32).reshape(K, n * B, S + 1)
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}

    def client_batch(self, batch: dict, c: int) -> dict:
        """Client ``c``'s rows of a round's batch: leaves ``(K, batch, ...)``."""
        B = self.mix["batch"]
        return {k: v[:, c * B:(c + 1) * B] for k, v in batch.items()}
