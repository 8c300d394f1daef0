"""Mean time to first token over every measured request, from when each
was due, on the client's clock. What a chat user feels first, but not an
end-to-end metric yet: a window holds 35 requests, whose mean moved by 15%
in one run of six when the longest prompt waited three seconds more, and
whose median moves by 5 to 7% with the phase the seed picks (PERF.md
section 6), more than a bound of 0.10 admits. It is set by the prompt's
ragged ticks, as `itl_p95_ms` is by one."""

NAME = "server.ttft_mean_ms"
UNIT = "ms"
LAYER = "server"
MOVES = "itl_p95_ms"


def read(run):
    return (run.get("client") or {}).get("ttft_mean_ms")
