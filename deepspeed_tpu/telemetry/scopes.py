"""The program's names for its own device work.

``scope(name)`` is a thin ``jax.named_scope``: every op traced inside it
carries the name in the ``op_name`` metadata of its HLO instruction, which
a device profile shows (XProf's trace viewer and op profile) and which
``sharding.jit.ProgramRecord.instruction_scopes()`` reads back from the
compiled program. It is metadata and nothing else: the optimized program
is the same instruction for instruction, nothing runs for it.

ONE vocabulary (:data:`SCOPES`), innermost name wins:

``embed``       token (+ position) embedding
``attn``        a softmax mixer: the norm before it, the q/k/v or latent
                projections, rotary, the core (kernel or einsum), the head
                transposes, the output projection, its residual add
``kda``         a KDA (gated delta rule) mixer, the same extent
``mlp``         the norm before the dense MLP, the MLP, its residual add
``moe``         router, sort / gather, grouped matmuls, shared expert, combine
``head``        final norm, the loss head / logits, the next token; of a
                block-diffusion step the confidences, the choice of
                positions and their transfer (``/unmask``)
``layers``      around ``layer_scan``: what the scan itself adds (weight
                slices, residual stacking, carries) is ``layers``, a
                block's ops inside it are the block's
``accumulate``  the engine's scan over micro-batches, by the same rule
``optimizer``   unscale, norm, clip, update, cast-back; a model's rule on
                the leaves the optimizer leaves alone (``/router_bias``)

A finer name may follow a vocabulary name after a ``/`` (:data:`FINER`:
``attn/core``, ``mlp/up``): it goes to the notes of a traced run, never to
a metric.

:func:`classify` reads an ``op_name`` back: the scope, and the pass the op
belongs to (``fwd``, ``bwd``, ``recompute``, ``none``).
"""

from __future__ import annotations

import re
from typing import Tuple

import jax

__all__ = ["SCOPES", "FINER", "PASSES", "scope", "classify"]

SCOPES = ("embed", "attn", "kda", "mlp", "moe", "head", "layers",
          "accumulate", "optimizer")
FINER = ("qkv", "core", "out", "up", "down", "router", "experts", "shared",
         "gnorm", "update", "cast", "router_bias", "unmask")
PASSES = ("fwd", "bwd", "recompute", "none")

# jax writes a transform around the scope entered outside it:
# ``transpose(jvp(head))/mul``; a ``jit(name)`` segment is a function's
# name, never a scope
_TRANSFORMS = frozenset(("jvp", "transpose", "vmap"))
_named_scope = jax.named_scope


def scope(name: str):
    """``with scope("attn"):`` / ``with scope("attn/core"):``."""
    head, _, finer = name.partition("/")
    if head not in SCOPES or (finer and finer not in FINER):
        raise ValueError(f"scope {name!r}: not <{'|'.join(SCOPES)}>"
                         f"[/<{'|'.join(FINER)}>]")
    return _named_scope(name)


def classify(op_name: str, instruction: str = "") -> Tuple[str, str]:
    """``op_name`` (an HLO instruction's metadata) -> ``(scope, pass)``.

    ``scope``: the innermost vocabulary name of the path, with the finer
    name that directly follows it (``"attn/core"``), or ``""``. The path
    splits on ``/`` and on the brackets of the transforms.
    ``pass``: ``recompute`` where the path holds ``rematted_computation``
    (``jax.checkpoint``'s re-run) or ``instruction``, the HLO instruction's
    name, holds ``.remat`` (XLA's own rematerialization); else ``bwd`` under
    a ``transpose(``, ``fwd`` under a ``jvp(``, ``none`` outside both (the
    optimizer, a served program)."""
    found = ""
    segments = op_name.split("/")
    for i, segment in enumerate(segments):
        *wrappers, inner = re.findall(r"[^()]+", segment) or [""]
        if inner in SCOPES and _TRANSFORMS.issuperset(wrappers):
            found = inner
            if i + 1 < len(segments) and segments[i + 1] in FINER:
                found += "/" + segments[i + 1]
    if "rematted_computation" in segments or ".remat" in instruction:
        return found, "recompute"
    if "transpose(" in op_name:
        return found, "bwd"
    return found, "fwd" if "jvp(" in op_name else "none"
