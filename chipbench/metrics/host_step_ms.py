"""Host milliseconds of one decode step outside its wait for the sampled
token: over the window's ``Engine.step`` spans that admit no request (hold
no ``Engine.prefill``), the mean of the span's time less that of its
``Engine.wait_token``.  None where the program opens no such spans."""
import bisect


def _inside(spans, starts, s, e):
    i = bisect.bisect_left(starts, s)
    out = []
    while i < len(spans) and spans[i][0] < e:
        if spans[i][1] <= e:
            out.append(spans[i])
        i += 1
    return out


def read(run):
    tr = run.trace
    if tr is None or tr.window is None:
        return None
    w0, w1 = tr.window
    by = {n: sorted((s, e) for name, s, e in tr.spans if name == n)
          for n in ("Engine.step", "Engine.prefill", "Engine.wait_token")}
    starts = {n: [s for s, _ in v] for n, v in by.items()}
    host = []
    for s, e in by["Engine.step"]:
        if s < w0 or e > w1:
            continue
        if _inside(by["Engine.prefill"], starts["Engine.prefill"], s, e):
            continue
        wait = _inside(by["Engine.wait_token"], starts["Engine.wait_token"],
                       s, e)
        host.append((e - s) - sum(b - a for a, b in wait))
    if not host:
        return None
    return sum(host) / len(host) / 1e6
