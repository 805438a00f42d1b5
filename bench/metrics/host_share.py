"""Share of the scheduler's tick time (``sched.tick`` span) in the window
that the serving program spent on the host rather than blocked on the
device (the ``*.wait`` spans)."""
from named import span_s, wait_s
from readers import share


def read(rec):
    tick = span_s(rec, "sched.tick")
    return share(tick - wait_s(rec), tick)
