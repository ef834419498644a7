"""What the two kernels a hybrid state-space / expert model adds must do
at the least, from the sizes of a call alone (``kernel_costs.py`` has
the dense int8 matmul's; ``least_seconds`` there turns a cost into the
device's least time).

Least means least: every byte the algorithm cannot avoid, once, and no
byte that a better kernel could avoid. A roofline share built on these
can read low, never above 100%.
"""

from __future__ import annotations


def ssm_scan(rows: float, tokens: float, heads: int, head_dim: int,
             state: int, groups: int, conv_taps: int) -> dict:
    """One state-space layer of one step program: ``rows`` live rows
    (decode rows, chunk lanes) that together carry ``tokens`` tokens.
    Every row's state S ``[heads, head_dim, state]`` is read once and
    written once in float32, as are the ``conv_taps - 1`` rows of the
    convolution's input it carries (bf16); every token's z, xBC and dt
    come in and its y goes out in bf16. The operations are the
    sequential recurrence's: a decay, an outer-product update and a
    read-out of the state a token, ``6 * heads * head_dim * state``."""
    inner = heads * head_dim
    conv_dim = inner + 2 * groups * state
    cell = heads * head_dim * state
    return {
        "ops": 6 * tokens * cell,
        "bytes": (
            rows * (2 * 4 * cell + 2 * 2 * (conv_taps - 1) * conv_dim)
            + 2 * tokens * (inner + conv_dim + heads + inner)
        ),
    }


def moe_relu2_matmul(tokens: float, assignments: float, experts: float,
                     d_model: int, d_ff_stored: int) -> dict:
    """One ungated expert layer's routed part: ``assignments`` (token,
    expert) pairs over ``experts`` experts that some token reached. Each
    reached expert's TWO int8 matrices (up: ``d_model x d_ff``; down:
    ``d_ff x d_model``, at the width they are stored at) are read once
    with their f32 column scales; the layer's ``tokens`` activation rows
    (bf16) come in once and go out once. The products run on the bf16
    units."""
    matrix = d_model * d_ff_stored
    return {
        "ops": 2 * assignments * 2 * matrix,
        "bytes": (experts * (2 * matrix + 4 * (d_ff_stored + d_model))
                  + 2 * 2 * tokens * d_model),
    }
