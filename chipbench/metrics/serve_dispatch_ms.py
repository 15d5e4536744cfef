"""Mean host time per flush in the engine call, outside the waits: each
traced ``repro.serve.engine`` span less the time its thread spent in
``repro.serve.wait`` (the device) and ``repro.serve.fetch`` (device to
host) inside it.

In the benchmark the engine call runs inside the harness's own
``chipbench.engine_call`` span, which blocks until the answer is ready
(``kinds/serve.TimedServer``): the part of that span outside the
program's ``repro.serve.dispatch`` is waiting on the device as well, and
is left out too."""
from collections import defaultdict

from chipbench import scopes


def _inside(thread, outer, name):
    """Spans called ``name`` within ``outer`` on its thread."""
    return [s for s in thread if s.name == name
            and outer.start_ns <= s.start_ns and s.end_ns <= outer.end_ns]


def _ns(spans):
    return sum(s.end_ns - s.start_ns for s in spans)


def read(run):
    t = scopes.of(run)
    if not t:
        return None
    threads = defaultdict(list)
    for s in t["spans"]:
        threads[s.thread].append(s)
    host = []
    for spans in threads.values():
        for e in (s for s in spans if s.name == "repro.serve.engine"):
            inner = [s for s in spans
                     if e.start_ns <= s.start_ns and s.end_ns <= e.end_ns]
            waited = _ns(_inside(inner, e, "repro.serve.wait")) \
                + _ns(_inside(inner, e, "repro.serve.fetch"))
            for call in _inside(inner, e, "chipbench.engine_call"):
                waited += call.end_ns - call.start_ns - _ns(
                    _inside(inner, call, "repro.serve.dispatch"))
            host.append(e.end_ns - e.start_ns - waited)
    return sum(host) / len(host) / 1e6 if host else None
