"""Smoke run of the instrumented training driver on TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # FSDP over four chips against one

Drives ``repro.launch.train`` in this process at rwkv6-3b's published width
(d=2560, 40x64 heads, d_ff=8960, vocab 65536) with only the depth cut, for
a few steps and two analysis windows, and checks what it returns: finite
losses starting near ln(vocab), the windows analyzed, and HLO flops recorded
in the step region.  With ``--chips 4`` it runs only the sharded path: the
same run FSDP over a (4, 1) mesh with ``--pod-gather``, compared step by
step with the same run on the first chip.

The printed numbers are smoke output, not benchmark metrics.  The last line
is one JSON object naming the device; it is printed only when every check
passed.  Without a TPU, or without the repository around it, the script
exits non-zero before any phase runs.
"""
import argparse
import gc
import json
import math
import os
import sys

ARCH, LAYERS, BATCH, SEQ = "rwkv6-3b", 4, 4, 1024
STEPS, WINDOW = 8, 4
RUN = ["--arch", ARCH, "--no-reduced", "--layers", str(LAYERS),
       "--batch", str(BATCH), "--seq", str(SEQ), "--steps", str(STEPS),
       "--analyze-every", str(WINDOW), "--schema", "tpu", "--costs", "hlo"]
COMPARED_STEPS = 4     # sharded run vs one chip: the first steps' losses
LOSS_RTOL = 1e-2       # bf16 compute, reduction order differs across meshes


def fail(msg: str) -> int:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    return 1


def check_run(res, label: str) -> str:
    """'' when the run's own outputs are sane, else what is wrong."""
    if not all(math.isfinite(x) for x in res.losses):
        return f"{label}: non-finite loss in {res.losses}"
    first = math.log(res.cfg.vocab_size)
    if abs(res.losses[0] - first) > 1.0:
        return (f"{label}: first loss {res.losses[0]:.4f} is not near "
                f"ln(vocab) = {first:.4f}")
    if res.windows_analyzed < 2:
        return f"{label}: {res.windows_analyzed} window(s) analyzed, want 2"
    if not res.step_attrs.get("hlo_flops", 0.0) > 0:
        return f"{label}: step region recorded no hlo_flops"
    return ""


def report(res, label: str) -> None:
    # the step region's recorded flops sum over the last window, and the
    # compiled module they come from is one device's share of the step
    flops = res.step_attrs["hlo_flops"] / WINDOW
    model = 6.0 * res.cfg.active_params() * BATCH * SEQ / len(res.bytes_in_use)
    print(f"[smoke {label}] compile {res.compile_s:.2f} s, median step "
          f"{res.median_step_ms:.2f} ms over {len(res.step_s)} steps",
          flush=True)
    print(f"[smoke {label}] losses {[round(x, 4) for x in res.losses]}",
          flush=True)
    print(f"[smoke {label}] [costs] coverage: {res.coverage}", flush=True)
    print(f"[smoke {label}] hlo_flops per step per device {flops:.4e} / "
          f"6*N_active*tokens per device {model:.4e} = {flops / model:.4f}",
          flush=True)
    print(f"[smoke {label}] bytes_in_use per device: {res.bytes_in_use}",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the FSDP path over four chips and the "
                         "one-chip run it is compared with")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    try:
        from repro.launch import steps, train
    except ImportError as e:
        return fail(f"the repro package is not next to this script ({e})")
    # before anything compiles
    print(f"[smoke] compile cache: {steps.use_compile_cache()}", flush=True)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(f"no TPU: JAX runs on {dev.platform}")
    if len(devices) < args.chips:
        return fail(f"{args.chips} chips asked, {len(devices)} present")
    print(f"[smoke] device_kind {dev.device_kind!r}, {len(devices)} "
          f"device(s); {ARCH} published width, depth cut to {LAYERS} "
          f"layers, batch {BATCH} x seq {SEQ}", flush=True)

    one = train.run(RUN + ["--devices", "1"])
    report(one, "1 chip")
    err = check_run(one, "1 chip")
    if err:
        return fail(err)

    if args.chips == 4:
        gc.collect()           # the one-chip state is gone before FSDP starts
        fsdp = train.run(RUN + ["--devices", "4", "--pod-gather"])
        report(fsdp, "4 chips FSDP")
        err = check_run(fsdp, "4 chips FSDP")
        if err:
            return fail(err)
        for i in range(COMPARED_STEPS):
            a, b = one.losses[i], fsdp.losses[i]
            print(f"[smoke] step {i + 1} loss: 1 chip {a:.5f}, 4 chips "
                  f"{b:.5f}, rel diff {abs(a - b) / abs(a):.2e}", flush=True)
            if abs(a - b) > LOSS_RTOL * abs(a):
                return fail(f"step {i + 1}: FSDP loss {b} vs one chip {a}")
        used = [b for b in fsdp.bytes_in_use if b]
        if len(used) != 4 or max(used) > 0.5 * sum(used):
            return fail(f"state not spread over four chips: "
                        f"{fsdp.bytes_in_use}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
