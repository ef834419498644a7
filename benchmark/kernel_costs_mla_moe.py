"""What the two kernels of a latent-attention, routed-expert model must
do at the least, from the sizes of a call alone (``kernel_costs.py`` has
the dense int8 matmul's; ``least_seconds`` there turns a cost into the
device's least time).

Least means least: every byte the algorithm cannot avoid, once, and no
byte that a better kernel could avoid. A roofline share built on these
can read low, never above 100%.
"""

from __future__ import annotations


def moe_grouped_matmul(tokens: float, assignments: float, experts: float,
                       d_model: int, d_ff: int) -> dict:
    """One expert layer's routed part: ``assignments`` (token, expert)
    pairs over ``experts`` experts that some token reached. Each reached
    expert's three int8 matrices (gate, up: ``d_model x d_ff``; down:
    ``d_ff x d_model``) are read once with their f32 column scales; the
    layer's ``tokens`` activation rows (bf16) come in once and go out
    once. The products run on the bf16 units."""
    matrix = d_model * d_ff
    return {
        "ops": 2 * assignments * 3 * matrix,
        "bytes": (experts * (3 * matrix + 4 * (2 * d_ff + d_model))
                  + 2 * 2 * tokens * d_model),
    }


def latent_attention(tokens_read: float, pool_lanes: int,
                     pairs: float = 0.0, heads: int = 16,
                     latent: int = 576, value: int = 512) -> dict:
    """One layer's absorbed latent attention: ``tokens_read`` cached
    tokens (a shared run once a group), each one latent of
    ``pool_lanes`` bf16 lanes (the pool's padded width: what a page's
    DMA moves); ``pairs`` (query, key) pairs, each ``heads`` dot
    products over the ``latent`` lanes and ``heads`` sums over the
    ``value`` lanes. Queries and outputs are small beside the pages and
    left out."""
    return {
        "ops": 2 * pairs * heads * (latent + value),
        "bytes": 2 * tokens_read * pool_lanes,
    }
