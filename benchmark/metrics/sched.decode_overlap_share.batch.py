"""Share of the window's decode calls dispatched with the call before them
still unread (%): of the `engine/decode` spans that carry `overlapped` (one a
launch), those where it is true.  A program that keeps no call in flight
records no such flag: then nothing to read."""

from benchmark.span_readers import Steps


def read(run):
    st = Steps(run)
    flags = [c["args"]["overlapped"] for s in st.steps
             for c in st.under(s, ("engine/decode",))
             if "overlapped" in c.get("args", {})]
    return 100.0 * sum(flags) / len(flags) if flags else None
