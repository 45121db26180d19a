"""Model operations of the embedder's forward pass over real tokens.

Per layer, for a sequence of ``n`` real tokens at width ``d``: the four
attention projections (8·n·d²), the GeGLU feed-forward (6·n·d·f) and the
attention scores and weighted sum (4·n²·d).  Padding and the token
lookup do no model work and are not counted.
"""
from __future__ import annotations


def encoder_flops(n_tokens, layers: int, d: int, f: int) -> float:
    """Model FLOPs of one forward over sequences of ``n_tokens`` real
    tokens each (an int or an iterable of ints)."""
    ns = [n_tokens] if isinstance(n_tokens, int) else list(n_tokens)
    per = sum(8 * n * d * d + 6 * n * d * f + 4 * n * n * d for n in ns)
    return float(layers * per)
