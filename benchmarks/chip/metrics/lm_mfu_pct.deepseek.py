"""Share of the chip's bf16 peak that a model with routed experts fills
over the traced window, %: the active-parameter work of the prefills and
decode ticks that started in it (``counts_moe``: latent attention, the
dense layer, the shared experts and the head for every token, the routed
experts for the assignments to held experts the program counted)."""
from benchmarks.chip import counts_moe
from benchmarks.chip.peaks import peaks_for


def read(rec):
    cfg = rec.cfg
    flops = 0
    for s in rec.in_trace("host_prefill"):
        flops += counts_moe.prefill_flops(cfg, s.attrs["n"]) + \
            counts_moe.routed_flops(cfg, s.attrs["moe_held"])
    for s in rec.in_trace("serve_tick"):
        flops += sum(counts_moe.decode_token_flops(cfg, c)
                     for c in s.attrs.get("contexts", ()))
        flops += counts_moe.routed_flops(
            cfg, sum(map(sum, s.attrs["moe_load"])))
    span = rec.traced[1] - rec.traced[0]
    if not flops or span <= 0:
        return None
    return 100.0 * flops / (span * peaks_for(rec.kind)["bf16_flops_per_s"])
