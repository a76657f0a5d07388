"""The plain reference, the lower-precision control and the comparison.

``evaluate`` runs a kernel's nodes one by one with numpy in the dtype it
is given: float32 is the reference for a float32 configuration, and
bfloat16 (each node's result and each constant rounded to bfloat16, as
``ml_dtypes`` does for numpy arithmetic) is the control, the next
precision below.  Every op is elementwise, so a slice of a block's answer
is the answer for that slice of the block.  Nothing here imports the
program.

``gap`` is the number compared: the largest ``|got - want| / max(1,
|want|)`` over a request's outputs, infinite for a missing or misshapen
output or a non-finite value where the reference has another.
"""

from __future__ import annotations

import numpy as np

OPS = {
    "BYP": lambda a, b, c: a,
    "ADD": lambda a, b, c: a + b,
    "SUB": lambda a, b, c: a - b,
    "MUL": lambda a, b, c: a * b,
    "ADDC": lambda a, b, c: a + c,
    "SUBC": lambda a, b, c: a - c,
    "RSUBC": lambda a, b, c: c - a,
    "MULC": lambda a, b, c: a * c,
    "SQR": lambda a, b, c: a * a,
    "MAX": lambda a, b, c: np.maximum(a, b),
    "MIN": lambda a, b, c: np.minimum(a, b),
    "ABS": lambda a, b, c: np.abs(a),
    "NEG": lambda a, b, c: -a,
}


def evaluate(spec, xs, dtype=np.float32) -> list[np.ndarray]:
    """Outputs of ``spec`` on inputs ``xs`` (one 1-D array per input)."""
    dtype = np.dtype(dtype)
    vals = {name: np.asarray(x).astype(dtype)
            for name, x in zip(spec.inputs, xs)}
    for name, op, args, imm in spec.nodes:
        a = vals[args[0]]
        b = vals[args[1]] if len(args) > 1 else a
        c = None if imm is None else dtype.type(imm)
        vals[name] = np.asarray(OPS[op](a, b, c)).astype(dtype)
    return [vals[o] for o in spec.outputs]


def bfloat16():
    """numpy's bfloat16 dtype (ml_dtypes, installed with jax)."""
    import ml_dtypes
    return ml_dtypes.bfloat16


def gap(got, want) -> float:
    """Widest relative gap of one request's outputs from the reference."""
    if got is None or len(got) != len(want):
        return float("inf")
    worst = 0.0
    for g, w in zip(got, want):
        g = np.asarray(g)
        if g.shape != w.shape:
            return float("inf")
        g = g.astype(np.float64)
        w = w.astype(np.float64)
        finite = np.isfinite(g) & np.isfinite(w)
        if not np.all(finite | (g == w)):
            return float("inf")
        if g.size:
            d = np.abs(g - w)[finite] / np.maximum(1.0, np.abs(w[finite]))
            worst = max(worst, float(d.max(initial=0.0)))
    return worst
