"""The kernel catalog of a configuration, as plain data.

A kernel is a feed-forward data-flow graph: named primary inputs, a list
of nodes in topological order, each ``[name, op, args, imm]``, and named
outputs.  The same ``KernelSpec`` feeds the program (``to_program``
builds the program's DFG from it) and the plain reference
(``reference.evaluate``), so neither reads what the other made.

A configuration's ``catalog`` names a file of kernels under
``chipbench/kernels/`` and a number of seeded random kernels.  The random
generator is the one the repository's property tests use
(``random_dfg`` in ``tests/test_property_fuzz.py``), emitting nodes with
the front end's constant folding: ``x op c`` becomes ADDC/SUBC/MULC and
``x * x`` becomes SQR.  A candidate is kept only when it fits one pass
(depth at most ``max_stages``), its outputs stay finite and bounded over
the input range, and no kernel before it has the same nodes.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

#: largest |output| a random kernel may reach on the input range; keeps
#: every kernel's values far from float32 overflow on any input draw
MAX_ABS_OUTPUT = 1.0e4


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    name: str
    inputs: tuple
    nodes: tuple          # ((name, op, (args...), imm), ...) topological
    outputs: tuple

    @property
    def depth(self) -> int:
        level = {i: 0 for i in self.inputs}
        for name, _, args, _ in self.nodes:
            level[name] = 1 + max(level[a] for a in args)
        return max((level[n[0]] for n in self.nodes), default=0)

    def structure(self) -> tuple:
        return (self.inputs, self.nodes, self.outputs)


def spec_from_json(obj: dict) -> KernelSpec:
    return KernelSpec(name=obj["name"], inputs=tuple(obj["inputs"]),
                      nodes=tuple((n, op, tuple(args), imm)
                                  for n, op, args, imm in obj["nodes"]),
                      outputs=tuple(obj["outputs"]))


def random_kernel(seed: int, max_stmts: int, name: str) -> KernelSpec:
    """``random_dfg(seed)`` of the property tests, as nodes."""
    rng = np.random.RandomState(seed)
    n_in = int(rng.randint(1, 6))
    n_stmt = int(rng.randint(1, max_stmts + 1))
    names = [f"x{i}" for i in range(n_in)]
    used: set = set()
    nodes = []
    for i in range(n_stmt):
        op = str(rng.choice(["+", "-", "*"]))
        a = names[rng.randint(len(names))]
        used.add(a)
        t = f"t{i}"
        if rng.rand() < 0.3:
            c = int(rng.randint(1, 9))
            nodes.append((t, {"+": "ADDC", "-": "SUBC", "*": "MULC"}[op],
                          (a,), c))
        else:
            b = names[rng.randint(len(names))]
            used.add(b)
            if op == "*" and a == b:
                nodes.append((t, "SQR", (a,), None))
            else:
                nodes.append((t, {"+": "ADD", "-": "SUB", "*": "MUL"}[op],
                              (a, b), None))
        names.append(t)
    out = f"t{n_stmt - 1}"
    for j, d in enumerate(n for n in names[:-1] if n not in used):
        nodes.append((f"f{j}", "ADD", (out, d), None))
        out = f"f{j}"
    return KernelSpec(name=name, inputs=tuple(f"x{i}" for i in range(n_in)),
                      nodes=tuple(nodes), outputs=(out,))


def _bounded(spec: KernelSpec, lo: float, hi: float) -> bool:
    """Outputs finite and within ``MAX_ABS_OUTPUT`` on a probe of the
    input box: its corners and centre per input, and random points."""
    from chipbench.reference import evaluate
    rng = np.random.RandomState(0)
    n = len(spec.inputs)
    probe = [np.concatenate([np.array([lo, hi, 0.0]),
                             rng.uniform(lo, hi, 4096)]) for _ in range(n)]
    for y in evaluate(spec, probe, np.float64):
        if not np.all(np.isfinite(y)) or np.max(np.abs(y)) > MAX_ABS_OUTPUT:
            return False
    return True


def build(catalog: dict, input_range) -> list[KernelSpec]:
    """The configuration's catalog: the named file's kernels, then the
    seeded random ones that pass the filters above."""
    specs = []
    if catalog.get("file"):
        data = json.loads((HERE / "kernels" / catalog["file"]).read_text())
        specs = [spec_from_json(k) for k in data["kernels"]]
    seen = {s.structure() for s in specs}
    want = len(specs) + int(catalog.get("random", 0))
    lo, hi = input_range
    seed = int(catalog.get("seed", 0))
    while len(specs) < want:
        cand = random_kernel(seed, int(catalog["max_statements"]),
                             f"r{seed}")
        seed += 1
        if (cand.depth <= int(catalog["max_stages"])
                and cand.structure() not in seen and _bounded(cand, lo, hi)):
            seen.add(cand.structure())
            specs.append(cand)
    return specs


def to_program(spec: KernelSpec):
    """The program's compiled kernel for ``spec`` (DFG -> schedule ->
    encoded context), through the program's own mapping flow."""
    from repro.core.dfg import DFG, Node, Op
    from repro.core.overlay import compile_program
    dfg = DFG.build(spec.name, spec.inputs,
                    [Node(n, Op[op], tuple(args), imm)
                     for n, op, args, imm in spec.nodes], spec.outputs)
    return compile_program(dfg)
