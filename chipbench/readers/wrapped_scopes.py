"""A scope's share of the step for a ``jax.named_scope`` the program enters
outside any ``jit`` of its own (``models/mellum.py``'s ``rsdl.lm.norm`` and
``rsdl.lm.rope``, PR 47: a ``jit`` around a norm would change the older
configurations' programs). JAX then writes the scope's name inside the
transform's on the operations of the forward pass, ``jvp(rsdl.lm.norm)/mul``,
and as a component of its own on the recomputed and the backward ones,
``.../checkpoint/rematted_computation/rsdl.lm.norm/mul``;
``xplane.under_scope`` knows the second form. Both are the scope's: the
first is rewritten into the second and the accepted reader does the rest.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

from chipbench.readers import device

_WRAPPED = re.compile(r"\((rsdl\.[\w.]+?)(\)+)")


def unwrapped(op_name: str) -> str:
    """``jvp(rsdl.x)/mul`` -> ``jvp()/rsdl.x/mul``; ``transpose(jvp(
    rsdl.x))/mul`` -> ``transpose(jvp())/rsdl.x/mul``; anything else as it
    is."""
    return _WRAPPED.sub(r"(\2/\1", op_name)


def scope_pct_of_step(facts: Dict[str, Any], scope: str, module: str
                      ) -> Optional[float]:
    """``device.scope_pct_of_step`` over the step's instructions' names
    with a wrapped scope taken out of its transform's name. A program
    without the scope (the parent's), or an untraced run: ``None``."""
    names = facts.get("step_op_names")
    if not names:
        return None
    return device.scope_pct_of_step(
        dict(facts, step_op_names={instruction: unwrapped(op_name)
                                   for instruction, op_name in names.items()}),
        scope, module)
