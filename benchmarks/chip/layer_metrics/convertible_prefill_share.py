"""Share of the sampled requests' prompt tokens that no KV transfer
delivered: prefilled on a Convertible Decoder, whole or in chunks.  Read
from where the router put each request (a prefiller's queue, whose every
request reaches its decoder by a transfer, or a Convertible Decoder), over
the whole window.  Layer: the cluster (router, scaler, instances)."""


def read(run):
    routes, outcomes = run.loop.routes, run.loop.outcomes
    sampled = [r for r in run.requests if r.rid in outcomes]
    total = sum(len(r.prompt) for r in sampled)
    if not total:
        return None
    local = sum(len(r.prompt) for r in sampled
                if routes.get(r.rid) == "convertible")
    return 100.0 * local / total
