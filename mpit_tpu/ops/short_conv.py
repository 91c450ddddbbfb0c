"""The short causal depthwise convolution of a token mixer: LFM2's gated
``conv`` layers (``models/transformer.py`` ``Lfm2Block``, three taps)
the three on q, k and v before Kimi's delta attention
(``delta_attention``, four taps), and the one over x, B and C together
in front of a state-space layer's scan (``NemotronBlock``'s ``mamba``
mixer: four taps, a bias a channel and a SiLU,
:func:`causal_conv_silu`).

``c[t] = sum_j taps[j] * u[t - (K - 1) + j]`` per channel, ``u`` zero
before the sequence: the last tap multiplies the current position and
nothing later is seen (torch's ``Conv1d(groups=d, padding=K - 1)`` cut
to the sequence's length).  ``K`` is a handful (3 or 4), so the convolution
is ``K`` shifted elementwise products that XLA fuses with the gates
around it; its transpose is the same shifts the other way, so the
backward pass has no scatter.  No state crosses sequences: a packed
grid's rows are whole sequences.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_depthwise_conv(u: jnp.ndarray, taps: jnp.ndarray) -> jnp.ndarray:
    """``u (B, L, d)`` convolved along ``L`` with ``taps (K, d)``, one
    filter a channel, causal (the module's docstring has the index
    convention); any ``L``, shorter than ``K`` included."""
    k, length = taps.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + length] for j in range(k))


def causal_conv_silu(u: jnp.ndarray, taps: jnp.ndarray,
                     bias: jnp.ndarray) -> jnp.ndarray:
    """``SiLU(conv(u) + bias)``: :func:`causal_depthwise_conv` with a
    bias ``(d,)`` a channel (``use_conv_bias``) and the activation that
    follows it, one elementwise pass over ``u`` once XLA has fused the
    shifts, the sum and the gate."""
    return jax.nn.silu(causal_depthwise_conv(u, taps) + bias)
