"""Share of the traced window, in percent, in which the first device is idle
during the host's own time in the program span named ``span``: the span less
the spans inside it that give it as their ``parent``, so the phases' shares
add up. The program's ``OpProfiler`` spans are ``TraceAnnotation`` events on
the trace's host plane, so both sides are on the trace's own clock
(``benchmarks/lib/xscope.py``). A trace without that span reads nothing."""
from benchmarks.lib import xscope


def read(params, obs):
    planes = xscope.traced() if obs.trace else None
    if not planes or not xscope.host_intervals(planes, params["span"]):
        return None
    under_s, window_s = xscope.idle_under(planes, params["span"])
    return 100.0 * under_s / window_s
