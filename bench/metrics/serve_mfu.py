"""serve_mfu: the embedder's model FLOPs on the real (unpadded) tokens
the window served, per second of window, as a share of the chip's bf16
peak.  The program pads every call to its fixed rows and tokens; that
padding is not counted, so the share says how much of the chip the
served questions used."""
from harness.peaks import peaks
from ops.encoder import encoder_flops


def read(ctx):
    if not ctx.get("tokens") or not ctx.get("trace"):
        return None
    m = ctx["encoder"]
    flops = encoder_flops(ctx["tokens"], m["num_hidden_layers"],
                          m["hidden_size"], m["intermediate_size"])
    return 100.0 * flops / ctx["window_s"] / peaks(
        ctx["device_kind"])["bf16_flops"]
