"""Share of the HBM roofline that a decode step's latent-cache read reaches:
the bytes its ``cgx_dequantize_flat`` calls must move (both streams of every
layer, ``benchmark/bytes_latent.py``) over the published HBM rate, over the
device time those calls took per decode step in the traced window. Bound:
HBM (no arithmetic to speak of). Only the decode step dequantizes."""

from benchmark import bytes_latent, readers


def read(ctx):
    seconds = readers.seconds_per(
        ctx, lambda n: n.startswith("cgx_dequantize"), "traced_decode_steps")
    if seconds is None or "kv_lora_rank" not in ctx["config"]:
        return None
    least = (bytes_latent.latent_step_bytes(ctx["config"])
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
