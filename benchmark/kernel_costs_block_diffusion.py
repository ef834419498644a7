"""What the ragged attention kernel must do at the least in a
block-diffusion model's programs, from the counts the program keeps
(``kernel_costs.py`` has the int8 matmul's; the expert layer's is
``kernel_costs_mla_moe.moe_grouped_matmul``, unchanged).

Least means least: every byte and product the algorithm cannot avoid,
once. A roofline share built on these can read low, never above 100%.
"""

from __future__ import annotations


def block_attention(pages: float, pairs: float, layers: int, page: int,
                    token_bytes: int, heads: int, head_dim: int) -> dict:
    """The attention of ``layers`` layers: each reads ``pages`` pool
    pages of ``page`` tokens (a group's shared run once), a token's K
    and V ``token_bytes`` a layer (bf16), and computes ``pairs`` (query,
    key) pairs under the model's rule, each ``heads`` dot products over
    ``head_dim`` for the score and as many sums for the value. Queries
    and outputs are small beside the pages and left out."""
    return {
        "ops": 4 * pairs * heads * head_dim * layers,
        "bytes": pages * page * token_bytes * layers,
    }
