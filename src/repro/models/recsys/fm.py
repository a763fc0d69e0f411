"""Factorization Machine [Rendle, ICDM'10] — the assigned recsys arch.

Config: 39 sparse fields, embed_dim 10, 2-way FM interactions via the
O(n*k) sum-square identity:

    sum_{i<j} <v_i, v_j> x_i x_j = 0.5 * ( (sum_i v_i)^2 - sum_i v_i^2 )

The hot path is the embedding LOOKUP over huge tables.  JAX has no native
EmbeddingBag; ours is jnp.take + reduce (and the Pallas scalar-prefetch
kernel in repro.kernels.embedding_bag for the TPU row-gather).  Tables are
sharded over the model axis by ROW (hash-partitioned vocab), the classic
recsys table-parallel layout.

Vocab: per-field sizes follow a Criteo-like power-law (few huge id fields,
many small categoricals), hashed into a single fused table with per-field
offsets — one gather for all 39 fields.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.models.common import normal_init


@dataclasses.dataclass(frozen=True)
class FMConfig:
    name: str = "fm"
    n_fields: int = 39
    embed_dim: int = 10
    total_vocab: int = 10_000_000  # fused table rows (Criteo-scale)
    interaction: str = "fm-2way"

    def field_vocabs(self) -> np.ndarray:
        """Per-field vocab sizes, power-law distributed, summing ~total."""
        ranks = np.arange(1, self.n_fields + 1, dtype=np.float64)
        w = ranks**-1.2
        sizes = np.maximum((w / w.sum() * self.total_vocab).astype(np.int64), 4)
        return sizes

    def field_offsets(self) -> np.ndarray:
        sizes = self.field_vocabs()
        return np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)

    @property
    def table_rows(self) -> int:
        # padded to a multiple of 512 so the row dim shards on any mesh axis
        raw = int(self.field_vocabs().sum())
        return -(-raw // 512) * 512


def init_params(cfg: FMConfig, key) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    rows = cfg.table_rows
    return {
        # 2nd-order factor table + 1st-order weight table (fused rows)
        "emb": normal_init(k1, (rows, cfg.embed_dim), 0.01),
        "lin": normal_init(k2, (rows, 1), 0.01),
        "bias": jnp.zeros((), jnp.float32),
    }


def _flat_ids(cfg: FMConfig, ids: jax.Array) -> jax.Array:
    """Per-field ids -> fused table rows. ids: int32[B, F]."""
    offs = jnp.asarray(cfg.field_offsets(), jnp.int32)
    sizes = jnp.asarray(cfg.field_vocabs(), jnp.int32)
    return offs[None, :] + jnp.remainder(ids, sizes[None, :])


def forward(cfg: FMConfig, params: dict, ids: jax.Array) -> jax.Array:
    """Logits [B] for a batch of multi-field categorical rows int32[B, F]."""
    rows = _flat_ids(cfg, ids)
    v = params["emb"][rows]  # (B, F, k)  <- THE hot gather
    lin = params["lin"][rows][..., 0]  # (B, F)
    sum_v = v.sum(axis=1)  # (B, k)
    sum_sq = (v * v).sum(axis=1)  # (B, k)
    pairwise = 0.5 * (sum_v * sum_v - sum_sq).sum(axis=-1)  # (B,)
    return params["bias"] + lin.sum(axis=-1) + pairwise


def bce_loss(cfg: FMConfig, params: dict, ids: jax.Array,
             labels: jax.Array) -> jax.Array:
    logits = forward(cfg, params, ids)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def retrieval_scores(cfg: FMConfig, params: dict, query_ids: jax.Array,
                     cand_ids: jax.Array) -> jax.Array:
    """Score ONE query against N candidate items without a python loop.

    query_ids: int32[Fq] user-side fields; cand_ids: int32[N, Fc] item-side
    fields. FM decomposes: score(u, c) = fm(u) + fm(c) + <sum_v(u), sum_v(c)>
    so candidate scoring is one batched matvec over precomputed candidate
    aggregates — this is what makes 1M-candidate retrieval a single GEMV.
    """
    q = forward(cfg, params, query_ids[None, :])  # (1,)
    c = forward(cfg, params, cand_ids)  # (N,)
    vq = params["emb"][_flat_ids(cfg, query_ids[None, :])].sum(axis=1)  # (1, k)
    vc = params["emb"][_flat_ids(cfg, cand_ids)].sum(axis=1)  # (N, k)
    cross = (vc @ vq[0]).astype(jnp.float32)  # (N,)
    return q + c + cross


def forward_with_kernel(cfg: FMConfig, params: dict, ids: jax.Array,
                        *, interpret: bool) -> jax.Array:
    """Same as forward() but the gather+reduce runs through the Pallas
    embedding_bag kernel (sum_v directly; squares via a second bag)."""
    from repro.kernels.embedding_bag import embedding_bag

    rows = _flat_ids(cfg, ids)
    k = cfg.embed_dim
    pad = (-k) % 128  # lane alignment for the TPU kernel
    emb = jnp.pad(params["emb"], ((0, 0), (0, pad)))
    sum_v = embedding_bag(emb, rows, interpret=interpret)[:, :k]
    sum_sq = embedding_bag(emb * emb, rows, interpret=interpret)[:, :k]
    lin = embedding_bag(
        jnp.pad(params["lin"], ((0, 0), (0, 127))), rows, interpret=interpret
    )[:, 0]
    pairwise = 0.5 * (sum_v * sum_v - sum_sq).sum(axis=-1)
    return params["bias"] + lin + pairwise
