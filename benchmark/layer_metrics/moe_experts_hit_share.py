"""Layer: model step (the sparse expert layers under it). Of the routed
experts an expert layer holds, the share that a decode step's picks
reach: the mean over decode steps and expert layers of the experts with
at least one row (`paged_stats["moe_experts_hit"]`, summed on the
device by the steps themselves over real rows alone) over the experts
of a layer. It is what sets the weight bytes a decode step must read:
an expert nobody picked is not streamed. A program without the counter,
or a family without experts, reads as nothing.
"""


def compute(record):
    hit = (record["paged"] or {}).get("moe_experts_hit")
    shape = record.get("shape") or {}
    layers, experts = shape.get("expert_layers"), shape.get("experts")
    if hit is None or not layers or not experts or not record["decode_steps"]:
        return None
    return 100.0 * hit / (record["decode_steps"] * layers * experts)
