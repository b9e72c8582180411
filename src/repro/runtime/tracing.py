"""Host spans and device scopes of the serve path, by name.

Host spans are `jax.profiler.TraceAnnotation`s, on the profiler's own
clock beside the device ops; with no profiler running each costs about
a microsecond. They take no arguments: nesting in time says which pump
a span belongs to.

  serve.pump        `ThresholdServer.pump`, the whole superstep
  serve.ingest      ring drain, address resolve, value stacking, the
                    host truth update (before the flush dispatch)
  engine.scatter    `set_votes`: the data and touched-row scatters
  engine.react      `set_votes`: the event-react dispatch
  engine.dispatch   `step`: the superstep dispatch (and fault sweep)
  engine.knowledge  `outputs`: the knowledge-output dispatch
  engine.readback   `outputs`: the blocking device-to-host copy
  serve.diff        `DecisionNotifier.publish`: diff, transitions
  serve.deliver     `DecisionNotifier.publish`: subscriber callbacks
  serve.account     `pump`: convergence check, epoch bookkeeping

Device scopes are `jax.named_scope`s over the sections of one engine
cycle (`JaxEngine._cycle_impl`); they set only the HLO metadata
(`op_name=".../cycle.<phase>/..."`), which the compiled program keeps
and `op_phases` maps back to its instructions.
"""
from __future__ import annotations

import contextlib
import re
from typing import Dict

SPANS = ("serve.pump", "serve.ingest", "engine.scatter", "engine.react",
         "engine.dispatch", "engine.knowledge", "engine.readback",
         "serve.diff", "serve.deliver", "serve.account")

PHASES = ("cycle.scan", "cycle.descent", "cycle.accept", "cycle.react",
          "cycle.wheel", "cycle.stage", "cycle.probe", "cycle.append",
          "cycle.account")

_annotation = None


def span(name: str):
    """A host span `name` in the profiler's trace (JAX imported on the
    first call, so numpy-only users of the serve layer import none)."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation as _annotation
    return _annotation(name)


@contextlib.contextmanager
def phases():
    """Sequential device scopes over one function's sections: inside
    the block, `phase(name)` closes the scope that is open and opens
    `name`; leaving the block closes the last."""
    import jax

    with contextlib.ExitStack() as stack:
        def phase(name: str) -> None:
            stack.close()
            stack.enter_context(jax.named_scope(name))

        yield phase


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_SCOPE = re.compile(r'op_name="[^"]*?/(cycle\.[a-z]+)[/"]')
_REF = re.compile(r"%([\w.\-]+)")


def op_phases(hlo_text: str) -> Dict[str, str]:
    """{HLO instruction name: cycle phase} of a compiled program's text.

    An instruction whose metadata names a cycle scope has that phase.
    One the compiler made without metadata (a copy, the reduce-window of
    a cumsum, a buffer) takes the phase of the first instruction it
    reads that has one, else of the first that reads it."""
    phase: Dict[str, str] = {}
    reads: Dict[str, list] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        reads[m.group(1)] = _REF.findall(line, m.end())
        hit = _SCOPE.search(line, m.end())
        if hit:
            phase[m.group(1)] = hit.group(1)
    users: Dict[str, list] = {name: [] for name in reads}
    for name, refs in reads.items():
        for r in refs:
            if r in users:
                users[r].append(name)
    for near in (reads, users, reads):
        grew = True
        while grew:
            grew = False
            for name in reads:
                if name not in phase:
                    p = next((phase[r] for r in near[name] if r in phase),
                             None)
                    if p is not None:
                        phase[name] = p
                        grew = True
    return phase
