"""Offline trace/recorder-dump summarizer (docs/observability.md).

Turns an ``Observability.dump_to()`` JSON file (or the crash-dump file
the engine writes on an unhandled exception) into a human-readable
report: per-request latency breakdown (queue wait, prefill time,
decode dispatches, preemptions, end-to-end), the shed/quarantine
tally, the degradation-ladder timeline, recorded incidents, and the
headline metric quantiles. The consumer of a dead run's
post-mortem, runnable anywhere (stdlib only — no jax import)::

    python tools/trace_summary.py run_dump.json

``tests/test_observability.py::
test_trace_summary_reports_lifecycle_and_tallies`` drives the dump ->
summarize pipeline end to end in tier 1, so it is not first exercised
at the incident.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List


def _fmt_s(v) -> str:
    return f"{float(v):.6f}s"


def _request_rows(timelines: Dict[str, List[Dict]]) -> List[Dict]:
    rows = []
    for uid in sorted(timelines):
        evs = timelines[uid]
        if not evs:
            continue
        submit = next((e["t"] for e in evs if e["type"] == "enqueue"),
                      evs[0]["t"])
        terminal = [e for e in evs if e["type"] == "terminal"]
        status = terminal[-1].get("status") if terminal else "in-flight"
        end = terminal[-1]["t"] if terminal else evs[-1]["t"]
        rows.append({
            "uid": uid,
            "status": status,
            "wait_s": sum(e.get("wait_s", 0.0) for e in evs
                          if e["type"] == "admit"),
            "prefill_chunks": sum(e["type"] == "prefill_chunk"
                                  for e in evs),
            "prefill_s": sum(e.get("dur_s", 0.0) for e in evs
                             if e["type"] == "prefill_chunk"),
            "dispatches": sum(e["type"] == "decode" for e in evs),
            "decode_tokens": sum(e.get("tokens", 0) for e in evs
                                 if e["type"] == "drain"),
            "preemptions": sum(e["type"] == "preempt" for e in evs),
            "sheds": [e.get("reason") for e in evs
                      if e["type"] == "shed"],
            "total_s": max(0.0, end - submit),
        })
    return rows


def summarize(dump: Dict) -> str:
    """The report, as one printable string (also the programmatic
    surface the tier-1 tests assert on)."""
    lines: List[str] = ["== apex_tpu observability dump summary =="]
    if dump.get("error"):
        lines.append(f"CRASH DUMP: {dump['error']}")
    trace = dump.get("trace") or {}
    rec = dump.get("recorder") or {}
    lines.append(
        f"trace: {trace.get('num_events', 0)} events "
        f"({trace.get('dropped', 0)} dropped) | recorder: "
        f"{len(rec.get('events', ()))} events "
        f"({rec.get('dropped', 0)} dropped, "
        f"{len(rec.get('incidents', ()))} incidents)")

    rows = _request_rows(trace.get("timelines") or {})
    if rows:
        lines.append(f"-- per-request lifecycle ({len(rows)} requests)")
        for r in rows:
            shed = (f" shed={','.join(map(str, r['sheds']))}"
                    if r["sheds"] else "")
            lines.append(
                f"  {r['uid']}: {r['status']} | wait {_fmt_s(r['wait_s'])}"
                f" | prefill {_fmt_s(r['prefill_s'])}"
                f" ({r['prefill_chunks']} chunks) | {r['dispatches']}"
                f" dispatches -> {r['decode_tokens']} decode tokens | "
                f"{r['preemptions']} preemptions | total "
                f"{_fmt_s(r['total_s'])}{shed}")

    shed_tally: Dict[str, int] = {}
    for evs in (trace.get("timelines") or {}).values():
        for e in evs:
            if e["type"] == "shed":
                reason = str(e.get("reason"))
                shed_tally[reason] = shed_tally.get(reason, 0) + 1
    lines.append("-- shed tally: " + (", ".join(
        f"{k}={v}" for k, v in sorted(shed_tally.items()))
        if shed_tally else "none"))

    rec_events = rec.get("events") or []
    quar = [e for e in rec_events
            if e.get("kind") in ("quarantine", "drafter_quarantine")]
    lines.append(
        "-- quarantines: " + (", ".join(
            f"{e['kind']}({e.get('uid', '-')}) @ {_fmt_s(e['t'])}"
            for e in quar) if quar else "none"))
    ladder = [e for e in rec_events if e.get("kind") == "ladder"]
    lines.append("-- ladder timeline: " + (" ; ".join(
        f"{_fmt_s(e['t'])} {e.get('direction')} -> rung {e.get('level')}"
        for e in ladder) if ladder else "no transitions"))
    resets = [e for e in rec_events if e.get("kind") == "device_reset"]
    if resets:
        lines.append(f"-- device resets: {len(resets)}")
    downs = [e for e in rec_events if e.get("kind") == "replica_down"]
    fails = [e for e in rec_events if e.get("kind") == "failover"]
    migs = [e for e in rec_events if e.get("kind") == "migrate"]
    if downs or fails or migs:
        lines.append(
            f"-- fleet: {len(downs)} replicas down "
            f"({', '.join(str(e.get('reason')) for e in downs)}), "
            f"{len(fails)} failovers re-homing "
            f"{sum(int(e.get('rehomed', 0)) for e in fails)} requests "
            f"(+{sum(int(e.get('adopted', 0)) for e in fails)} results "
            f"adopted from checkpoints), {len(migs)} migrations moving "
            f"{sum(int(e.get('requests', 0)) for e in migs)} requests")
    handoffs = [e for e in rec_events
                if e.get("kind") == "prefill_handoff"]
    if handoffs:
        last = handoffs[-1]
        lines.append(
            f"-- disaggregation: {len(handoffs)} handoff sweeps moving "
            f"{sum(int(e.get('requests', 0)) for e in handoffs)} "
            f"requests prefill->decode "
            f"({sum(int(e.get('bytes', 0)) for e in handoffs)} payload "
            f"bytes); queue depths at last handoff: "
            f"prefill={last.get('prefill_queue', 0)} "
            f"decode={last.get('decode_queue', 0)}")
    spawns = [e for e in rec_events if e.get("kind") == "replica_spawn"]
    retires = [e for e in rec_events
               if e.get("kind") == "replica_retire"]
    rpc_tos = [e for e in rec_events if e.get("kind") == "rpc_timeout"]
    if spawns or retires or rpc_tos:
        grew = ", ".join(f"r{e.get('replica')} @ {_fmt_s(e['t'])}"
                         for e in spawns) or "-"
        shrank = ", ".join(f"r{e.get('replica')} @ {_fmt_s(e['t'])}"
                           for e in retires) or "-"
        lines.append(
            f"-- autoscaler: {len(spawns)} spawns ({grew}), "
            f"{len(retires)} retires ({shrank}), "
            f"{len(rpc_tos)} rpc timeouts")
    spills = [e for e in rec_events if e.get("kind") == "spill"]
    uploads = [e for e in rec_events if e.get("kind") == "spill_upload"]
    if spills or uploads:
        lines.append(
            f"-- spill tier: {len(spills)} blocks spilled "
            f"({sum(int(e.get('bytes', 0)) for e in spills)} bytes), "
            f"{sum(int(e.get('blocks', 0)) for e in uploads)} blocks "
            f"re-admitted by upload across {len(uploads)} admissions")
    dequants = [e for e in rec_events if e.get("kind") == "dequant_gemm"]
    if dequants:
        e = dequants[-1]
        fp_b = int(e.get("fp_bytes", 0))
        q_b = int(e.get("quant_bytes", 0))
        ratio = (fp_b / q_b) if q_b else 0.0
        lines.append(
            f"-- weight quantization: mode={e.get('mode')} "
            f"({fp_b} fp param bytes -> {q_b} quantized, "
            f"{ratio:.2f}x smaller)")
    pubs = [e for e in rec_events if e.get("kind") == "shared_publish"]
    shits = [e for e in rec_events if e.get("kind") == "shared_hit"]
    if pubs or shits:
        lines.append(
            f"-- shared prefix tier: {len(pubs)} publish sweeps storing "
            f"{sum(int(e.get('blocks', 0)) for e in pubs)} blocks "
            f"({sum(int(e.get('bytes', 0)) for e in pubs)} bytes), "
            f"{sum(int(e.get('blocks', 0)) for e in shits)} blocks "
            f"seeded into replicas across {len(shits)} hits")
    tsteps = [e for e in rec_events if e.get("kind") == "train_step"]
    meshed = [e for e in tsteps if e.get("mesh")]
    if meshed:
        shape = "x".join(str(int(d)) for d in meshed[-1]["mesh"])
        span = sum(float(e.get("host_span_s", 0.0)) for e in meshed)
        lines.append(
            f"-- sharded train: {len(meshed)}/{len(tsteps)} steps "
            f"dispatched on the (batch, model)=({shape}) mesh "
            f"({_fmt_s(span)} host span)")
    scrubs = [e for e in rec_events if e.get("kind") == "scrub"]
    corrupts = [e for e in rec_events
                if e.get("kind") == "corruption_detected"]
    suspects = [e for e in rec_events if e.get("kind") == "sdc_suspect"]
    if scrubs or corrupts or suspects:
        sites: Dict[str, int] = {}
        for e in corrupts:
            s = str(e.get("site"))
            sites[s] = sites.get(s, 0) + 1
        by_site = (" (" + ", ".join(f"{k}={v}" for k, v in
                                    sorted(sites.items())) + ")"
                   if sites else "")
        retired = (" (" + ", ".join(f"replica {e.get('replica')}"
                                    for e in suspects) + ")"
                   if suspects else "")
        lines.append(
            f"-- integrity: {len(scrubs)} scrubs verifying "
            f"{sum(int(e.get('verified', 0)) for e in scrubs)} blocks, "
            f"{len(corrupts)} corruptions caught{by_site}, "
            f"{len(suspects)} SDC suspects retired{retired}")
    incidents = rec.get("incidents") or []
    for inc in incidents:
        lines.append(
            f"-- incident {inc.get('label')!r} @ {_fmt_s(inc.get('t', 0))}"
            f" ({len(inc.get('events', ()))} events frozen)")

    values = (dump.get("metrics") or {}).get("values") or {}
    if values:
        parts = []
        for name in ("serving_ttft_s", "serving_itl_s",
                     "serving_queue_wait_s", "train_step_s"):
            h = values.get(name)
            if isinstance(h, dict) and h.get("count"):
                parts.append(f"{name} p50={h['p50']:.6f} "
                             f"p99={h['p99']:.6f} (n={h['count']})")
        for name in ("serving_requests_total", "serving_tokens_total",
                     "serving_sheds_total", "serving_preemptions_total",
                     "train_steps_total"):
            if name in values:
                parts.append(f"{name}={values[name]:g}")
        if parts:
            lines.append("-- metrics: " + " | ".join(parts))
    return "\n".join(lines)


def summarize_file(path: str) -> str:
    with open(path, encoding="utf-8") as f:
        return summarize(json.load(f))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python tools/trace_summary.py <dump.json>",
              file=sys.stderr)
        return 2
    print(summarize_file(argv[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
