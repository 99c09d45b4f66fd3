"""The longest single gap between two consecutive step completions, over
the part of the window before the profiler starts (starting it stops the
train thread, and is no stall of the program's), the first completion
left out (it waits for the empty pipeline to fill: a host call and a
whole step after the window's start). One reading of the host's clock,
good to half a millisecond: it is there to show a stall of a tenth of a
second, which ``step_ms_p95`` hides while fewer than one span in twenty
holds one."""


def read(r):
    gaps = r.untraced_gaps[1:]
    return 1e3 * max(gaps) if gaps else None
