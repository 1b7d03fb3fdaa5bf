"""IP-address anonymization — paper §IV "IP Address Anonymization".

The paper's recipe, verbatim in data-science ops:

  1. ``unique`` over the union of src and dst columns  -> N distinct IPs,
  2. generate ``iota(N)`` and ``shuffle`` it  -> random permutation,
  3. ``gather`` new ids for every row.

Step 1 also returns each row's rank among the N IPs, as
``np.unique(return_inverse=True)`` does: the unique sort carries each
endpoint's position, and its segment ids are scattered back to the rows
(``plan.unique_concat_inverse``), so step 3 is a rank off that sort and a
gather — no binary search of the rows over the IP domain.

We provide the stochastic variant (``cupy.random.shuffle`` analogue via
``jax.random``) and the deterministic HashGraph-style variant the paper cites
as future work (Green et al. [22, 23]) — both over static-shape buffers.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .ops import hash_permutation, random_permutation
from .plan import unique_concat_inverse
from .table import Table

__all__ = ["AnonymizationResult", "anonymize"]


@dataclasses.dataclass(frozen=True)
class AnonymizationResult:
    table: Table           # same schema, src/dst replaced by anonymized ids
    ip_values: jnp.ndarray  # sorted distinct original IPs (tail-padded)
    new_ids: jnp.ndarray    # new_ids[rank] = anonymized id of ip_values[rank]
    n_ips: jnp.ndarray      # scalar int32


jax.tree_util.register_pytree_node(
    AnonymizationResult,
    lambda a: ((a.table, a.ip_values, a.new_ids, a.n_ips), None),
    lambda _, ch: AnonymizationResult(*ch),
)


def anonymize(
    t: Table,
    key: Optional[jax.Array] = None,
    *,
    method: str = "shuffle",
    rounds: int = 1,
) -> AnonymizationResult:
    """Anonymize ``src``/``dst`` of a packet table.

    Args:
      t: packet table with ``src`` and ``dst`` columns.
      key: PRNG key (required for ``method='shuffle'``).
      method: ``'shuffle'`` (paper's cupy.random.shuffle analogue) or
        ``'hash'`` (deterministic HashGraph-style permutation, Green et al.).
      rounds: extra shuffle rounds — the paper notes one or two extra
        iterations further decorrelate the permutation at negligible cost.
    """
    # device scopes name the paper's three steps in the profiler's op
    # metadata: anonymize/unique, /factorize (the rank scatter off the
    # unique sort), /permutation, /gather
    with jax.named_scope("anonymize"):
        ips, src_rank, dst_rank = unique_concat_inverse(
            t["src"], t["dst"], t.n_valid)
        cap = ips.keys[0].shape[0]
        n = ips.n_groups
        with jax.named_scope("permutation"):
            if method == "shuffle":
                if key is None:
                    raise ValueError("method='shuffle' requires a PRNG key")
                keys = jax.random.split(key, rounds)
                perm = random_permutation(keys[0], cap, n)
                for k in keys[1:]:
                    # composing uniform permutations == shuffling again
                    # (paper §IV)
                    perm = perm[random_permutation(k, cap, n)]
            elif method == "hash":
                perm = hash_permutation(cap, n)
                for r in range(1, rounds):
                    perm = perm[hash_permutation(cap, n, salt=0x9E3779B9 + r)]
            else:
                raise ValueError(f"unknown method {method!r}")

        with jax.named_scope("gather"):
            anon = t.with_columns(src=perm[src_rank], dst=perm[dst_rank])
    return AnonymizationResult(table=anon, ip_values=ips.keys[0], new_ids=perm,
                               n_ips=n)
