"""Device time of the search program's named stages, from a traced window.

The program names its stages inside ``jit`` with ``jax.named_scope``
(``vdms.probe``, ``vdms.segment_topk``, ``vdms.gid_map``, ``vdms.merge``,
``vdms.cluster_of``), which the compiled HLO keeps in the ``op_name`` of
each instruction's metadata; a fusion keeps its root's. A device op in the
trace is named by its HLO instruction (``bench/trace.py`` ``op_name``), so the
compiled program's text maps each op of the trace to a stage. The text comes
from ``VDMSInstance.search_program``; a program without it, or without the
scopes, has no stages here and its metrics read nothing.
"""
from __future__ import annotations

import re

from bench import trace as trace_mod

SCOPE = "vdms."
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_STAGE = re.compile(re.escape(SCOPE) + r"(\w+)")


def op_stages(hlo_text: str) -> dict:
    """``{instruction name: stage}`` for each instruction of an HLO module's
    text whose ``op_name`` holds a ``vdms.<stage>``; the innermost one counts."""
    out = {}
    for line in hlo_text.splitlines():
        head, meta = _INSTRUCTION.match(line), _OP_NAME.search(line)
        if head and meta:
            stages = _STAGE.findall(meta.group(1))
            if stages:
                out[head.group(1)] = stages[-1]
    return out


def _per_call(ctx) -> dict | None:
    """``{stage: [device self seconds of its ops in each search_call]}`` of
    the traced window, or None where there is nothing to read. Kept on
    ``ctx``: each stage's metric reads the same reduction."""
    if hasattr(ctx, "_stage_seconds"):
        return ctx._stage_seconds
    tr, found = ctx.trace, None
    calls = tr.spans_named("search_call") if tr is not None and tr.ops else []
    program = getattr(ctx.searcher, "search_program", None)
    if calls and program is not None:
        mix = ctx.mix
        width = int(mix["request_queries"] if mix["loop"] == "closed" else mix["max_batch"])
        stages = op_stages(program(width, int(mix["topk"])).as_text())
        found = {stage: [] for stage in set(stages.values())}
        for _, a, b in calls:
            call = dict.fromkeys(found, 0.0)
            for name, self_s in trace_mod.top_ops(tr, a, b, len(tr.ops)):
                if name in stages:
                    call[stages[name]] += self_s
            for stage, seconds in call.items():
                found[stage].append(seconds)
    ctx._stage_seconds = found
    return found


def stage_ms(ctx, stage: str) -> float | None:
    """Mean over the traced window's search calls of the device self time of
    the ops that the program's scope ``vdms.<stage>`` names, in ms; None where
    no op of the program maps to the stage."""
    per_call = _per_call(ctx)
    if not per_call or stage not in per_call:
        return None
    seconds = per_call[stage]
    return 1e3 * sum(seconds) / len(seconds)
