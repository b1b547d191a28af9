"""One-command hardware validation of the kernel planes.

Run on the chip (plain `python tools/hw_validate.py`; it raises at start
when JAX finds no TPU). `JAX_PLATFORMS=cpu CEDAR_HWVAL_SMALL=1` is the
harness smoke: shrunk shapes, pallas in interpret mode. Prints one JSON
line with:

  * `pallas_buckets`: for the DEFAULT TPU plane (bf16 pallas) at the 10k
    shape, every batch bucket `pallas_supported` admits x {words kernel,
    first/last kernel} x {has_gate off, on} — does Mosaic compile it, and
    are its outputs byte-identical to the XLA plane's on random rows;
  * `pallas_bf16` / `pallas_int8`: the same equality through the engine
    entry point (the int8-in-pallas plane stays opt-in until this reports
    ok), and `segred`: the segmented-reduction plane against the scan
    plane;
  * int8 vs bf16 device-resident match rates at the headline shape
    (10k policies, 131072-row super-batches), and the scan / segred /
    pallas rates beside them. A rate printed by a cpu run is a harness
    check, not a measurement.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, ".")


def main() -> int:
    from cedar_tpu.jaxenv import (
        configure_compile_cache,
        cpu_requested,
        require_tpu,
    )

    configure_compile_cache()
    if not cpu_requested():
        require_tpu()

    import numpy as np

    import jax

    from bench import build_policy_set
    from cedar_tpu.engine.evaluator import _BATCH_BUCKETS, TPUPolicyEngine
    from cedar_tpu.ops.match import match_rules_codes, match_rules_codes_pallas
    from cedar_tpu.ops.pallas_match import pallas_supported

    # CEDAR_HWVAL_SMALL=1 shrinks shapes for a CPU smoke of the harness
    small = os.environ.get("CEDAR_HWVAL_SMALL", "0") == "1"
    dev = jax.devices()[0]
    out: dict = {
        "ok": True,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": len(jax.devices()),
    }
    ps, users, nss, resources, verbs, groups = build_policy_set(
        300 if small else 10_000
    )

    SB = 4096 if small else 131072

    def timed_rate(one, rows: int) -> float:
        """One pipelined timing pass: 6 async dispatches of `one()`
        (a device call returning the words array) drained together —
        the SAME harness for every plane and shape so rates stay
        comparable."""
        n_pipe = 6
        t = time.time()
        ws = []
        for _ in range(n_pipe):
            w = one()
            w.copy_to_host_async()
            ws.append(w)
        for w in ws:
            np.asarray(w)
        return rows * n_pipe / (time.time() - t)

    def median3(one, rows=None) -> int:
        np.asarray(one())  # compile + warm
        rows = SB if rows is None else rows
        return round(sorted(timed_rate(one, rows) for _ in range(3))[1])

    def device_rate(env_val: str) -> int:
        os.environ["CEDAR_TPU_INT8"] = env_val
        engine = TPUPolicyEngine()
        engine.load([ps], warm="off")
        cs = engine._compiled
        packed = cs.packed
        S = packed.table.n_slots
        codes = np.zeros((SB, S), dtype=cs.code_dtype)
        extras = np.full((SB, 8), packed.L, dtype=cs.active_dtype)
        args = (
            cs.act_rows_dev, cs.W_dev, cs.thresh_dev,
            cs.rule_group_dev, cs.rule_policy_dev,
        )
        cb, eb = jax.device_put(codes), jax.device_put(extras)
        return median3(
            lambda: match_rules_codes(
                cb, eb, *args, packed.n_tiers, False
            )[0]
        )

    rates = {}
    for env_val, key in (("1", "int8"), ("0", "bf16")):
        rates[key] = device_rate(env_val)

    def plane_rate(segred: bool, rows: int) -> int:
        """int8 plane at a given batch shape, scan or segmented kernel.
        BOTH shapes matter: the serving path dispatches <= 16384-row
        chunks (fastpath._CHUNK) while the bench headline runs
        131072-row super-batches — on the CPU backend the segmented
        plane wins the former and loses the latter (memory pressure
        from the unrolled per-chunk score intermediates), so the flip
        decision needs the TPU number for each regime."""
        os.environ["CEDAR_TPU_INT8"] = "1"
        engine = TPUPolicyEngine(segred=segred)
        engine.load([ps], warm="off")
        cs = engine._compiled
        packed = cs.packed
        S = packed.table.n_slots
        codes = np.zeros((rows, S), dtype=cs.code_dtype)
        extras = np.full((rows, 8), packed.L, dtype=cs.active_dtype)
        args = (
            cs.act_rows_dev, cs.W_dev, cs.thresh_dev,
            cs.rule_group_dev, cs.rule_policy_dev,
        )
        cb, eb = jax.device_put(codes), jax.device_put(extras)

        return median3(
            lambda: match_rules_codes(
                cb, eb, *args, packed.n_tiers, False, False, None,
                packed.has_gate, cs.segs,
            )[0],
            rows=rows,
        )

    serving_rows = 2048 if small else 16384
    for key, segred, rows in (
        ("segred_int8_resident_rate", True, SB),
        ("segred_serving_rate", True, serving_rows),
        ("scan_serving_rate", False, serving_rows),
    ):
        try:
            out[key] = plane_rate(segred, rows)
        except Exception as e:  # noqa: BLE001 — report, don't crash
            out[key] = f"error: {type(e).__name__}: {e}"
    if isinstance(out.get("segred_int8_resident_rate"), int):
        out["segred_vs_scan_speedup"] = round(
            out["segred_int8_resident_rate"] / max(rates["int8"], 1), 3
        )
    if isinstance(out.get("segred_serving_rate"), int) and isinstance(
        out.get("scan_serving_rate"), int
    ):
        out["segred_vs_scan_serving_speedup"] = round(
            out["segred_serving_rate"] / max(out["scan_serving_rate"], 1), 3
        )
    out["device_resident_rate_int8"] = rates["int8"]
    out["device_resident_rate_bf16"] = rates["bf16"]
    out["int8_speedup"] = round(rates["int8"] / max(rates["bf16"], 1), 3)

    # ---- plane equality on the chip: compile + byte-identical outputs vs
    # the XLA scan plane. NOTE: the probes feed RANDOM codes, which violate
    # the u8 wire plan's per-slot-range precondition
    # (engine._CompiledSet.wire) — disable the wire for these engines so
    # every plane evaluates the same random rows.
    os.environ["CEDAR_TPU_INT8"] = "1"
    os.environ["CEDAR_TPU_WIRE_U8"] = "0"
    eng_xla = TPUPolicyEngine(use_pallas=False, segred=False)
    eng_xla.load([ps], warm="off")
    cs_x = eng_xla._compiled
    rng = np.random.default_rng(5)

    def random_rows(cs, B: int):
        S = cs.packed.table.n_slots
        codes = rng.integers(
            0, cs.packed.table.n_rows, size=(B, S)
        ).astype(cs.code_dtype)
        extras = np.full((B, 8), cs.packed.L, dtype=cs.active_dtype)
        return codes, extras

    def verdict(same: bool) -> str:
        return "ok" if same else "MISMATCH"

    def engine_equal(eng, B: int = 256) -> str:
        """match_arrays words of `eng` vs the XLA scan engine."""
        cs = eng._compiled
        codes, extras = random_rows(cs, B)
        w = eng.match_arrays(codes, extras, cs=cs)[0]
        w_x = eng_xla.match_arrays(codes, extras, cs=cs_x)[0]
        return verdict(bool((np.asarray(w) == np.asarray(w_x)).all()))

    for key, env in (
        ("pallas_bf16", {"CEDAR_TPU_PALLAS_INT8": "0"}),
        ("pallas_int8", {"CEDAR_TPU_PALLAS_INT8": "1"}),
    ):
        os.environ.update(env)
        try:
            eng_pl = TPUPolicyEngine(use_pallas=True)
            eng_pl.load([ps], warm="off")
            if eng_pl._compiled.pallas_args is None:
                out[key] = "unsupported-shape"
                continue
            out[key] = engine_equal(eng_pl)
        except Exception as e:  # noqa: BLE001 — report, don't crash the probe
            out[key] = f"error: {type(e).__name__}: {e}"
    os.environ["CEDAR_TPU_PALLAS_INT8"] = "0"
    try:
        eng_seg = TPUPolicyEngine(use_pallas=False, segred=True)
        eng_seg.load([ps], warm="off")
        out["segred"] = engine_equal(eng_seg, serving_rows)
    except Exception as e:  # noqa: BLE001
        out["segred"] = f"error: {type(e).__name__}: {e}"

    # the default TPU plane, bucket by bucket: every batch bucket the
    # serving path can hand the pallas kernels (pallas_supported), the
    # fused words kernel (want_full off) and the first/last kernel
    # (want_full on), with and without the gate group — the corpus decides
    # has_gate in production, so both must lower.
    buckets: dict = {}
    out["pallas_buckets"] = buckets
    try:
        eng_pl = TPUPolicyEngine(use_pallas=True)
        eng_pl.load([ps], warm="off")
        cs = eng_pl._compiled
        packed = cs.packed
        x_args = (
            cs_x.act_rows_dev, cs_x.W_dev, cs_x.thresh_dev,
            cs_x.rule_group_dev, cs_x.rule_policy_dev,
        )
        top = 2048 if small else 16384
        for B in (b for b in _BATCH_BUCKETS if b <= top):
            if cs.pallas_args is None or not pallas_supported(
                B, packed.L, packed.R
            ):
                buckets[str(B)] = "xla-plane (not tiled)"
                continue
            codes, extras = random_rows(cs, B)
            for want_full in (False, True):
                for has_gate in (False, True):
                    key = (
                        f"{B}/{'full' if want_full else 'words'}"
                        f"{'+gate' if has_gate else ''}"
                    )
                    try:
                        w, f = match_rules_codes_pallas(
                            codes, extras, cs.act_rows_dev,
                            *cs.pallas_args, packed.n_tiers, want_full,
                            eng_pl._pallas_interpret, has_gate,
                        )
                        w_x, f_x = match_rules_codes(
                            codes, extras, *x_args, packed.n_tiers,
                            want_full, False, None, has_gate, None,
                        )
                        same = bool(
                            (np.asarray(w) == np.asarray(w_x)).all()
                        )
                        if want_full:
                            same = same and all(
                                bool((np.asarray(a) == np.asarray(b)).all())
                                for a, b in zip(f, f_x)
                            )
                        buckets[key] = verdict(same)
                    except Exception as e:  # noqa: BLE001
                        buckets[key] = (
                            f"error: {type(e).__name__}: {str(e)[:400]}"
                        )
    except Exception as e:  # noqa: BLE001
        buckets["error"] = f"{type(e).__name__}: {e}"

    # pallas int8 THROUGHPUT at the headline shape: the fused kernel keeps
    # score tiles in VMEM (no [B, R] HBM round trip between the matmul and
    # the per-group first-match reduction).
    if dev.platform == "cpu":
        out["pallas_int8_resident_rate"] = "skipped-cpu (interpret mode)"
    else:
        try:
            os.environ["CEDAR_TPU_PALLAS_INT8"] = "1"
            eng = TPUPolicyEngine(use_pallas=True)
            eng.load([ps], warm="off")
            cs = eng._compiled
            packed = cs.packed
            if cs.pallas_args is None or not pallas_supported(
                SB, packed.L, packed.R
            ):
                out["pallas_int8_resident_rate"] = "unsupported-shape"
            else:
                S = packed.table.n_slots
                codes = np.zeros((SB, S), dtype=cs.code_dtype)
                extras = np.full((SB, 8), packed.L, dtype=cs.active_dtype)
                cb, eb = jax.device_put(codes), jax.device_put(extras)
                rate = median3(
                    lambda: match_rules_codes_pallas(
                        cb, eb, cs.act_rows_dev, *cs.pallas_args,
                        packed.n_tiers, False, False, packed.has_gate,
                    )[0]
                )
                out["pallas_int8_resident_rate"] = rate
                out["pallas_vs_xla_speedup"] = round(
                    rate / max(rates["int8"], 1), 3
                )
        except Exception as e:  # noqa: BLE001
            out["pallas_int8_resident_rate"] = (
                f"error: {type(e).__name__}: {e}"
            )
    checks = [out.get("pallas_bf16"), out.get("pallas_int8"), out.get("segred")]
    checks += list(buckets.values())
    out["ok"] = all(
        isinstance(c, str) and (c == "ok" or c.startswith("xla-plane"))
        for c in checks
    )
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
