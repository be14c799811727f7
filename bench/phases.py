"""Device seconds per phase of the FedALIGN round, from the compiled round's
optimized HLO text and a reduced trace (``trace.reduce``).

The round names its phases with ``jax.named_scope``: ``fedalign.<phase>``
(``server_loss``, ``eval``, ``gate``, ``train``, ``aggregate``,
``server_step``), and its Pallas kernels ``kernel.<name>``. Those names
reach the optimized HLO as path components of each instruction's
``op_name`` metadata, but not the trace, whose operations are named by
the instruction's text alone. So the HLO gives each instruction a phase:

- the innermost ``fedalign.`` component of its ``op_name``;
- with no ``op_name``, the phase of the computation that holds it, where
  every named instruction of that computation has that one phase;
- anything else is ``unscoped``.

The join keys an instruction by its text up to its opcode's parenthesis
(``%name = <shape> opcode(``), which the trace's operation names start
with; operations of other programs in the window do not join. A phase's
seconds are the innermost operations' device seconds (mean over chips);
``unscoped`` is the rest of the busy time, so the phases sum to
``busy_s``.

The readers take the HLO from the programs loaded on the backend's
client (``live_executables``): of those that name a phase, the one whose
instructions join the most traced seconds is the round. A recorded run,
with no program loaded, passes its text as ``ctx["hlo"]``.
"""
from __future__ import annotations

import functools
import re
from collections import defaultdict

PHASE = re.compile(r"fedalign\.(\w+)")
KERNEL = re.compile(r"kernel\.(\w+)")
UNSCOPED = "unscoped"
# "%name = <shape> opcode(", as trace.py reads an operation's name
_INSTR = re.compile(r"^%?([\w.\-]+) = .*?[\s}\]]([a-z][a-z0-9\-]*)\(")
_LINE = re.compile(r"^\s+(?:ROOT\s+)?(%?[\w.\-]+ = .*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?[\w.\-]+ .*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _innermost(pattern, op_name):
    found = pattern.findall(op_name)
    return found[-1] if found else None


@functools.lru_cache(maxsize=4)
def instruction_phases(hlo_text):
    """{join key: (phase, kernel or None)} for every instruction of the
    HLO module's text."""
    out = {}
    comp = None                       # [(key, phase, kernel)] of one body
    for line in hlo_text.splitlines():
        if comp is None:
            if _COMPUTATION.match(line):
                comp = []
            continue
        if line.startswith("}"):
            named = {p for _, p, _ in comp if p is not None}
            whole = named.pop() if len(named) == 1 else None
            for key, phase, kernel in comp:
                out[key] = (phase if phase is not None
                            else whole or UNSCOPED, kernel)
            comp = None
            continue
        m = _LINE.match(line)
        instr = m and _INSTR.match(m.group(1))
        if not instr:
            continue
        op = _OP_NAME.search(line)
        op_name = op.group(1) if op else ""
        phase = _innermost(PHASE, op_name)
        if phase is None and op_name:
            phase = UNSCOPED
        comp.append((instr.group(0), phase, _innermost(KERNEL, op_name)))
    return out


def _key(op_name):
    m = _INSTR.match(op_name)
    return m.group(0) if m else None


def phase_seconds(hlo_text, reduced):
    """Device seconds (mean over chips) per phase and per kernel scope of
    the round whose optimized HLO is ``hlo_text``, in the window that
    ``reduced`` (``trace.reduce``'s output) covers. Returns {"phases":
    {phase: s} with ``unscoped`` = busy less the named phases, "kernels":
    {name: s}, "joined_s": leaf seconds that joined an instruction,
    "leaf_s": all leaf seconds}; None where the program names no phase."""
    if not hlo_text or "fedalign." not in hlo_text:
        return None
    table = instruction_phases(hlo_text)
    phases, kernels = defaultdict(float), defaultdict(float)
    joined = leaf = 0.0
    for name, s in reduced["op_seconds"].items():
        leaf += s
        hit = table.get(_key(name))
        if hit is None:
            continue
        joined += s
        phase, kernel = hit
        if phase != UNSCOPED:
            phases[phase] += s
        if kernel is not None:
            kernels[kernel] += s
    phases = dict(phases)
    phases[UNSCOPED] = reduced["busy_s"] - sum(phases.values())
    return {"phases": phases, "kernels": dict(kernels), "joined_s": joined,
            "leaf_s": leaf}


def loaded_texts():
    """The optimized HLO text of each program loaded on the default
    backend's client that names a phase."""
    import jax
    texts = []
    for exe in jax.devices()[0].client.live_executables():
        try:
            text = exe.get_hlo_text()
        except jax.errors.JaxRuntimeError:
            continue
        if "fedalign." in text:
            texts.append(text)
    return texts


def round_phases(ctx):
    """``phase_seconds`` of the traced round: of the candidate programs
    (``ctx["hlo"]`` where given, else the loaded ones), the one whose
    instructions join the most seconds of ``ctx["trace"]``; None where
    none names a phase or joins any."""
    texts = [ctx["hlo"]] if "hlo" in ctx else loaded_texts()
    best = None
    for text in texts:
        got = phase_seconds(text, ctx["trace"])
        if got and got["joined_s"] > (best["joined_s"] if best else 0.0):
            best = got
    return best


def round_ms(ctx, names):
    """Device ms per traced round in the phases ``names`` (``unscoped``
    included where named); None where the program names no phase or no
    operation ran on a device."""
    if not ctx["trace"]["busy_s"] or not ctx["rounds"]:
        return None
    got = round_phases(ctx)
    if got is None:
        return None
    return 1e3 * sum(got["phases"].get(n, 0.0) for n in names) \
        / len(ctx["rounds"])
