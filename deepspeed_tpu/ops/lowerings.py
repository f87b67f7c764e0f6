"""What the pickers chose while a program was traced: one registry.

A picker that has more than one lowering for an op (a Pallas kernel or its
``jax.numpy`` twin, a fused backward or a split one) says which it took with
:func:`count`, when traced; :func:`note` keeps the records that are no count.
Whoever traces a program (the engine, round a step program's first call; a
test) takes a :func:`snapshot` before and reads :func:`since` after, and never
learns a picker's name. The counts are the proof that a program ran the kernel
its roofline claims; nothing here is touched in a timed step.

The flash kernels' sites (``ops/flash_attention.py``) say which mask a call
ran under, because the masks the kernels take are few: none, the causal
diagonal, a sliding window beside it, and the diagonal rounded to blocks
(``diag``: block diffusion's two calls a layer). ``flash_bwd_tiles`` is keyed
by the mask (the window's length, ``"causal"``, ``"none"``, or
``"diag<n>"`` / ``"diag<n>_strict"``; ``"diag<n>_own"``: the own tiles of a
call with a second key source, which ``flash_own_keys`` counts as
``"operand"``), ``flash_diag_fwd_tiles`` by the rounded diagonal's label
alone, ``flash_fwd_tiles`` is the newest forward's whatever its mask;
``flash_bwd_arm`` and ``flash_bwd_segments`` are keyed by the head's width:
which backward the newest call at that width took, and in how many segments
of q-tiles the fused one worked a head (1: whole). A mask the kernels do not take leaves no record here: a call with
``segment_ids`` is ``xla_attention``'s (dense), and with ``diag`` besides it is
refused by name.
"""

from typing import Any, Dict, Tuple

_COUNTS: Dict[Tuple[str, str], int] = {}
#: (site, by) -> (when, value)
_NOTES: Dict[Tuple[str, Any], Tuple[int, Any]] = {}
_clock = [0]                                # notes written so far

Snapshot = Tuple[Dict[Tuple[str, str], int], int]


def count(site: str, answer: str, n: int = 1) -> None:
    """``site`` lowered ``n`` more of its ops as ``answer``."""
    _COUNTS[site, answer] = _COUNTS.get((site, answer), 0) + n


def note(site: str, value: Any, by: Any = None) -> None:
    """``site``'s newest record (the older one is forgotten); given ``by``,
    the newest under each ``by``, and the site reads as ``{by: value}``."""
    _clock[0] += 1
    _NOTES[site, by] = (_clock[0], value)


def snapshot() -> Snapshot:
    return dict(_COUNTS), _clock[0]


def since(snap: Snapshot) -> Dict[str, Any]:
    """``{site: {answer: n}}`` of what counted after ``snap``, and ``{site:
    value}`` (``{site: {by: value}}``) of the notes written after it: a site
    that counted nothing is absent, an answer that counted nothing is left
    out."""
    counts, clock = snap
    out: Dict[str, Any] = {}
    for (site, answer), n in _COUNTS.items():
        added = n - counts.get((site, answer), 0)
        if added:
            out.setdefault(site, {})[answer] = added
    for (site, by), (when, value) in _NOTES.items():
        if when <= clock:
            continue
        if by is None:
            out[site] = value
        else:
            out.setdefault(site, {})[by] = value
    return out
