"""The names of the train step's parts on the device trace.

Each name is a ``jax.named_scope`` around one part of the model or of the
step.  A scope is HLO metadata only: XLA keeps it in the ``op_name`` of
every operation the part lowers to, through remat, the layer scan and the
backward pass (where a path reads ``transpose(jvp(...))/.../attention/...``),
and no instruction changes.  A profile can then sum device time by part:
an operation belongs to the innermost of these names on its ``op_name``
path, and to none when no name is there.
"""

from __future__ import annotations

EMBED = "embed"            # token embedding and positions (_input_sequence)
ATTENTION = "attention"    # the whole attention mixer, cross-attention too
FFN = "ffn"                # dense feed-forward
MOE = "moe"                # routed experts
RECURRENT = "recurrent"    # recurrent mixers (RG-LRU, mLSTM, sLSTM)
LOSS = "loss"              # final norm and the chunked cross-entropy
OPTIMIZER = "optimizer"    # gradient clipping and the optimizer update

ALL = (EMBED, ATTENTION, FFN, MOE, RECURRENT, LOSS, OPTIMIZER)
