"""The yardstick for a kernel's roofline share: published peaks by
``device_kind``, and the operations and bytes one fused voting sweep needs
(``ops/voting.py`` ``_sweep_core``), computed from its shape bucket.

The peaks table is copied from ``bench.py`` (``_TPU_PEAK_FLOPS``) with the
memory bandwidth and the int8 peak added. A device that is not in the table
is an error, never a default.
"""

from __future__ import annotations

from typing import Dict, Tuple

PEAKS: Dict[str, dict] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add it to "
            "benchmark/harness/peaks.py PEAKS with its source"
        ) from None


def sweep_ops_bytes(W: int, E: int, P: int, S: int, R: int,
                    B: int = 1) -> Tuple[float, float]:
    """(operations, bytes) one execution of the fused sweep needs at the
    bucket (W witnesses, E events, P peers, S peer-set slots, R rounds),
    ``B`` windows to a vmapped execution. A multiply-add counts 2.

    Operations, following ``_fame_core`` / ``_sweep_core`` / ``_rr_core``:

    - see[w, x]: one gathered compare per (witness, event): W*E;
    - strongly-see counts: the [W, W, P] compare, W*W*P, and its
      contraction with the S membership masks, 2*S*W*W*P;
    - fame, R-1 voting rounds: the vote tally ``ss_prev @ votes`` is a
      [W, W] x [W, W] product, 2*W**3, and about 12 elementwise passes
      over [W, W];
    - decidedness: 4 passes over [R, W];
    - round-received, R-1 rounds: 3 passes over [W, E] and 8 over [E].

    Bytes are the least the program can move: every input read once and
    the output written once (everything between can stay on the chip).
    """
    ops = (
        W * E
        + W * W * P + 2 * S * W * W * P
        + (R - 1) * (2 * W**3 + 12 * W * W)
        + 4 * R * W
        + (R - 1) * (3 * W * E + 8 * E)
    )
    bytes_in = (
        4 * 3 * E + E  # creator, index, rounds_e int32; undet_e bool
        + 4 * 2 * W * P  # la_w, fd_w int32
        + 4 * 3 * W + 2 * W  # rounds_w, fame0_w, wit_idx; valid_w, mid_w
        + S * P + 4 * S  # member bool, sm_s
        + 4 * 2 * R + 3 * R  # psi, sm_r; exists_r, prior_dec_r, lb_gate_r
    )
    bytes_out = 4 * (W + E)
    return float(B * ops), float(B * (bytes_in + bytes_out))


def sweep_least_seconds(device_kind: str, W: int, E: int, P: int, S: int,
                        R: int, B: int = 1) -> Tuple[float, str]:
    """The least time the chip could take for one sweep execution, and
    which bound sets it (``compute`` or ``memory``). The tallies are int8
    products accumulated in int32 (``ops/intdot.py``), so the compute peak
    is the int8 one."""
    pk = peaks(device_kind)
    ops, nbytes = sweep_ops_bytes(W, E, P, S, R, B)
    t_ops, t_bytes = ops / pk["ops_int8"], nbytes / pk["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
