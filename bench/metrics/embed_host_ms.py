"""embed_host_ms: host time of the ``embed`` span per window request:
its wall time less the ``embed.sync`` spans inside it, the program's
waits for the encoder's embeddings to reach the host."""


def read(ctx):
    embed, sync = ctx["spans"].get("embed"), ctx["spans"].get("embed.sync")
    if not embed or not sync:
        return None
    return (sum(embed) - sum(sync)) / len(embed) * 1e3
