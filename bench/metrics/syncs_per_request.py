"""syncs_per_request: the program's waits for the device per window
request: spans named ``<stage>.sync`` (each one blocking copy or wait)
over ``request`` spans."""


def read(ctx):
    spans = ctx["spans"]
    n = sum(len(v) for k, v in spans.items() if k.endswith(".sync"))
    requests = spans.get("request")
    return n / len(requests) if n and requests else None
