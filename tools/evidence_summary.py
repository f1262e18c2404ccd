"""Render the repo's benchmark evidence as one markdown summary.

Ledger-driven since graft-evidence: the enumeration authority is
``EVIDENCE/ledger.jsonl`` (``grace_tpu.evidence``) — every capture a
writer has attested shows up here, keyed by its ledger records. Captures
with a dedicated reader below render as rich tables/prose (annotated
with the ledger ids README claim markers cite); captures *without* one
fall through to a generic ledger table, so a ``REGION_LAST.json``-style
new artifact stops requiring per-file reader code the day its writer
lands. Flight-recorder incidents get a one-line roll-up. Known artifacts
found on disk still render even before their first ledger record, so a
fresh checkout (or a test tmp dir) degrades to the pre-ledger behavior.

Dedicated readers exist for (all repo-root, all optional):
  BENCH_TPU_LAST.json      headline dense-vs-compressed pair (TPU)
  BENCH_ALL_TPU_LAST.json  per-algorithm TPU sweep
  BENCH_BERT_TPU_LAST.json BERT-base + PowerSGD rows
  BENCH_ALL_CPU.json       per-algorithm CPU-mesh smoke sweep
  TPU_VARIANTS.jsonl       selection-variant session rows
  LINT_LAST.json / PROF_LAST.json / ELASTIC_LAST.json /
  REGION_LAST.json / ADAPT_LAST.json / RETUNE_LAST.json /
  WATCH_LAST.json / TUNE_LAST.json

Usage: python tools/evidence_summary.py [--update-readme]
Prints markdown to stdout; --update-readme splices it between the
<!-- evidence:begin --> / <!-- evidence:end --> markers in README.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
BEGIN, END = "<!-- evidence:begin -->", "<!-- evidence:end -->"


def _staleness(doc):
    """bench.evidence_staleness — the ONE stale-evidence detector, shared
    with the bench's own last_tpu carry-along readers (itself a delegate
    to grace_tpu.evidence.staleness since graft-evidence)."""
    import bench
    return bench.evidence_staleness(doc)


def _stale_parts(doc):
    """(title_suffix, trailing_lines) for a possibly-stale evidence doc."""
    reasons = _staleness(doc)
    if not reasons:
        return "", []
    return " — ⚠ STALE — predates PRs 7–10", [
        "", "⚠ **STALE — predates PRs 7–10**: " + "; ".join(reasons)
        + ". The numbers above describe the pre-hier/pre-bucketed/"
          "pre-fused-pack system; refresh the capture with "
          "`python bench_all.py --tuned` at the next chip window."]


def _load(name):
    """Load a .json dict or a JSON-Lines row list (BENCH_ALL_CPU.json is
    JSONL despite its extension; rows whose only key is _meta are
    metadata, not data)."""
    try:
        with open(os.path.join(ROOT, name)) as f:
            text = f.read()
    except OSError:
        return None
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        rows = []
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                return None
        return rows or None


def _ledger_view():
    """(by_capture_basename, latest_by_id) over the repo ledger — empty
    dicts when no ledger exists (fresh checkout, test tmp dirs)."""
    path = os.path.join(ROOT, "EVIDENCE", "ledger.jsonl")
    try:
        from grace_tpu.evidence.ledger import latest_by_id, load_ledger
        latest = latest_by_id(load_ledger(path))
    except Exception:                                      # noqa: BLE001
        return {}, {}
    by_capture = {}
    for rec in latest.values():
        base = os.path.basename(str(rec.get("capture") or ""))
        if base:
            by_capture.setdefault(base, []).append(rec)
    for recs in by_capture.values():
        recs.sort(key=lambda r: (r.get("claim_class") or "",
                                 r.get("id") or ""))
    return by_capture, latest


def _ledger_note(recs):
    """One sub-line tying a rendered section to its ledger records — the
    same ids the README/CHANGELOG claim markers cite and graft_gate
    verifies."""
    if not recs:
        return []
    cite = ", ".join(f"`{r.get('id')}` [{r.get('claim_class', '?')}]"
                     for r in recs)
    return [f"<sub>ledger: {cite}</sub>"]


def _fmt(x, nd=2):
    return "—" if x is None else f"{x:.{nd}f}"


def _row_table(rows, title, value_key="imgs_per_sec",
               value_head="imgs/sec"):
    spread = any(r.get("spread_pct") is not None for r in rows)
    shead = " spread% |" if spread else ""
    out = [f"**{title}**", "",
           f"| config | {value_head} | vs dense | wire ratio | MFU |{shead}",
           "|---|---|---|---|---|" + ("---|" if spread else "")]
    rows = [r for r in rows if r.get("config")]   # skip _meta-style rows
    for r in rows:
        cfg_name = r.get("config") or ""
        # Scoped disables flag only the configs whose kernel family was
        # forced onto the staged path — keyed off the row's stamped
        # grace_params (ADVICE r4: a renamed config would silently lose
        # the caveat under name-substring matching; old rows without the
        # stamp keep the name fallback).
        compressor = (r.get("grace_params") or {}).get("compressor", "")
        flags = ""
        if r.get("env_pallas_disabled"):
            flags = " ⚠staged"
        elif r.get("env_pallas_quant_disabled") and (
                compressor == "qsgd" or
                (not compressor and "qsgd" in cfg_name)):
            flags = " ⚠staged-quant"
        elif r.get("env_pallas_topk_disabled") and (
                compressor == "topk" or
                (not compressor and "topk" in cfg_name)):
            flags = " ⚠staged-topk"
        if r.get("resumed"):
            flags += " ↻resumed"
        if r.get("error"):
            out.append(f"| {r.get('config')} | ERROR: {r['error'][:60]} |"
                       + " — |" * (3 + spread))
            continue
        scell = f" {_fmt(r.get('spread_pct'), 1)} |" if spread else ""
        out.append(
            f"| {r.get('config')}{flags} | {_fmt(r.get(value_key))} | "
            f"{_fmt(r.get('vs_baseline'), 4)} | "
            f"{_fmt(r.get('wire_ratio'), 4)} | {_fmt(r.get('mfu'), 4)} |"
            + scell)
    return out


def _curve_table():
    """Final-accuracy table over every committed curve TSV in
    examples/logs, read from each file's own provenance header (data
    source, config) and last data row — the files self-describe, so this
    can never quote a number the file does not contain."""
    import glob

    logs = sorted(glob.glob(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples", "logs", "*.tsv")))
    rows = []
    for path in logs:
        prov, header, last = {}, None, None
        try:
            with open(path) as f:
                for line in f:
                    line = line.rstrip("\n")
                    if line.startswith("# ") and ": " in line:
                        k, v = line[2:].split(": ", 1)
                        prov[k] = v
                    elif line and header is None:
                        header = line.split("\t")
                    elif line:
                        last = line.split("\t")
        except OSError:
            continue
        if not header or not last:
            continue
        rec = dict(zip(header, last))
        acc = rec.get("test_acc") or rec.get("top1Accuracy")
        data = prov.get("data", "?").split(" (")[0]   # drop inline caveats
        comm_s = prov.get("communicator", "?")
        if prov.get("fusion"):   # stamped since round 5; absent = pre-stamp
            comm_s += f" ({prov['fusion']})"
        rows.append((os.path.basename(path), data,
                     prov.get("compressor", "?"), prov.get("memory", "?"),
                     prov.get("memory_dtype", ""), comm_s,
                     rec.get("epoch", "?"), acc if acc is not None else "?"))
    if not rows:
        return []
    out = ["**Convergence curves (examples/logs — final row of each "
           "committed TSV; provenance from the file's own header)**", "",
           "| file | data | compressor | memory | communicator | epochs |"
           " final acc |", "|---|---|---|---|---|---|---|"]
    for (name, data, comp, mem, mdt, comm, ep, acc) in rows:
        mem_s = f"{mem}({mdt})" if mdt else mem
        out.append(f"| {name} | {data} | {comp} | {mem_s} | {comm} |"
                   f" {ep} | {acc} |")
    if any(n.startswith("cifar10_") and "synthetic" in n
           for (n, *_rest) in rows):
        out += ["",
                "The `cifar10_*_synthetic` curves run the full DAWNBench "
                "recipe on synthetic data: they are recipe-mechanics and "
                "compression-stability evidence only. The reference's "
                "94%/24-epoch CIFAR-10 accuracy target "
                "(`examples/dist/CIFAR10-dawndist/README.md:17`) is "
                "**unvalidated here** — this box has zero network egress "
                "and no cached CIFAR-10 binaries (pip, keras.datasets and "
                "tfds download channels all fail). The real-data "
                "convergence evidence is the MNIST-10k / sklearn-digits "
                "family above."]
    return out


# ---------------------------------------------------------------------------
# Per-capture readers. Each takes the memoizing loader and returns the
# section's lines ([] = skip). The _SECTIONS table below is the dispatch:
# a capture basename listed there renders rich; anything else the ledger
# names renders through _generic_section.

def _sec_headline(docs):
    head = docs("BENCH_TPU_LAST.json")
    if not (head and head.get("rows")):
        return []
    cap = head.get("captured_at", "?")
    chip = head.get("chip", "?")
    partial = " (PARTIAL)" if head.get("partial") else ""
    suffix, trailer = _stale_parts(head)
    return _row_table(
        head["rows"],
        f"TPU headline ({chip}, captured {cap}){partial}{suffix}") + trailer


def _sec_sweep(docs):
    sweep = docs("BENCH_ALL_TPU_LAST.json")
    if not (sweep and sweep.get("rows")):
        return []
    cap = sweep.get("captured_at", "?")
    partial = " (PARTIAL)" if sweep.get("partial") else ""
    suffix, trailer = _stale_parts(sweep)
    parts = _row_table(
        sweep["rows"], f"TPU per-algorithm sweep (captured {cap})"
        + partial + suffix)
    parts += trailer
    # Same-named rows measured under different stamped params (e.g. the
    # round-5 headline moving to per-leaf after the sweep captured the
    # fused pair) read as contradictions without a caveat.
    head = docs("BENCH_TPU_LAST.json")
    if head and head.get("rows"):
        hp = {r["config"]: r.get("grace_params") for r in head["rows"]
              if r.get("grace_params")}
        drift = [r["config"] for r in sweep["rows"]
                 if r.get("grace_params") and
                 hp.get(r.get("config")) not in (None,
                                                 r["grace_params"])]
        if drift:
            parts += ["", "Note: " + ", ".join(sorted(set(drift))) +
                      " above were captured under different params than "
                      "the same-named headline rows (each row stamps its "
                      "own `grace_params`; the headline is the "
                      "authoritative config)."]
    return parts


def _sec_variants(docs):
    variants = docs("TPU_VARIANTS.jsonl")
    if not variants:
        return []
    return _row_table(
        variants,
        "Top-K selection variants (TPU) — SUPERSEDED: cross-session "
        "ratios (the dense row was timed in another session); the "
        "same-session sweep above is the quotable record")


def _sec_bert(docs):
    bert = docs("BENCH_BERT_TPU_LAST.json")
    if not (bert and bert.get("rows")):
        return []
    cap = bert.get("captured_at", "?")
    partial = " (PARTIAL)" if bert.get("partial") else ""
    return _row_table(
        bert["rows"], f"BERT-base + PowerSGD r4 (captured {cap})"
        + partial, value_key="tokens_per_sec", value_head="tokens/sec")


def _sec_projection(docs):
    rec = docs("BENCH_TPU_LAST.json") or {}
    proj = next((r["projection"] for r in rec.get("rows", [])
                 if r.get("config") == "topk1pct" and r.get("projection")),
                None)
    if not proj:
        return []
    parts = ["**Projected multi-chip speedup vs dense (topk1pct, "
             "analytic wire model over measured single-chip step)**", "",
             "| world | recv bytes/rank | step ms (ICI) | speedup ICI "
             "| speedup DCN |", "|---|---|---|---|---|"]
    for p in proj:
        parts.append(f"| {p['world']} | {p['recv_bytes_per_rank']:,} | "
                     f"{p['step_ms_ici']} | "
                     f"{p['speedup_vs_dense_ici']} | "
                     f"{p['speedup_vs_dense_dcn']} |")
    return parts


def _sec_cpu(docs):
    cpu = docs("BENCH_ALL_CPU.json")
    if not isinstance(cpu, list):
        return []
    data_rows = [r for r in cpu
                 if r.get("config") and r.get("imgs_per_sec")]
    skipped = [r["config"] for r in cpu if r.get("skipped")]
    if not data_rows:
        return []
    skip_s = (f"; skipped on cpu: {', '.join(skipped)}"
              if skipped else "")
    return [f"CPU-mesh smoke sweep: {len(data_rows)} configs measured "
            "in `BENCH_ALL_CPU.json` (throughput ratios are host-bound "
            f"artifacts; the wire columns are the content{skip_s})."]


def _sec_lint(docs):
    lint = docs("LINT_LAST.json")
    if not (isinstance(lint, dict) and "errors" in lint):
        return []
    when = (lint.get("captured_at") or "").split("T")[0]
    counts = lint.get("pass_counts") or {}
    if counts:
        dirty = {p: n for p, n in counts.items() if n}
        per_pass = (f"; per-pass findings: "
                    + ", ".join(f"{p} {n}"
                                for p, n in sorted(dirty.items()))
                    if dirty else
                    f"; all {len(counts)} passes clean")
    else:
        per_pass = ""
    bounds = lint.get("overlap_bounds") or {}
    bound_s = ""
    if bounds:
        bound_s = ("; bucketed overlap bounds: " + ", ".join(
            f"{name} static≤{rep.get('static_overlap_bound')} "
            f"({rep.get('independent_chains')}/"
            f"{rep.get('expected_chains')} chains)"
            for name, rep in sorted(bounds.items())
            if isinstance(rep, dict) and "error" not in rep))
    return [
        f"Static analysis: `graft_lint --all-configs` → "
        f"{lint['errors']} error(s) / {lint.get('warnings', 0)} "
        f"warning(s) over {lint.get('configs_audited', '?')} configs + "
        f"{lint.get('rules_checked', '?')} repo rules"
        f"{per_pass}{bound_s} "
        f"(`LINT_LAST.json`{', ' + when if when else ''})."]


def _sec_prof(docs):
    prof = docs("PROF_LAST.json")
    if not (isinstance(prof, dict) and prof.get("stages_ms")):
        return []
    when = (prof.get("captured_at") or "").split("T")[0]
    top = max(prof["stages_ms"].items(), key=lambda kv: kv[1])
    ov = prof.get("overlap_fraction")
    steps = prof.get("step_times") or {}
    bits = [f"total device time {_fmt(prof.get('total_device_ms'), 3)} "
            f"ms, top stage {top[0]} ({_fmt(top[1], 3)} ms)"]
    if ov is not None:
        bits.append(f"overlap fraction {100.0 * ov:.1f}%")
    sand = prof.get("overlap_sandwich")
    if isinstance(sand, dict):
        verdict = ("VIOLATED" if sand.get("violations") else "holds")
        bits.append(
            f"measured≤static sandwich vs {sand.get('config')} "
            f"(bound {sand.get('static_overlap_bound')}): {verdict}")
    if steps.get("p50_ms") is not None:
        bits.append(f"step p50 {_fmt(steps['p50_ms'], 3)} ms")
    regr = prof.get("regressions")
    if regr is not None:
        bits.append(f"{len(regr)} baseline regression(s)")
    note = f" — {prof['note']}" if prof.get("note") else ""
    return [
        f"Performance attribution: `perf_report --trace "
        f"{prof.get('trace', '?')}` → " + ", ".join(bits) +
        f" (`PROF_LAST.json`{', ' + when if when else ''}){note}."]


def _sec_elastic(docs):
    elastic = docs("ELASTIC_LAST.json")
    if not (isinstance(elastic, dict)
            and elastic.get("tool") == "chaos_smoke"):
        return []
    when = (elastic.get("captured_at") or "").split("T")[0]
    cycle = " → ".join(str(w) for w in (elastic.get("world_cycle") or []))
    resizes = elastic.get("resize_events") or []
    rejoin = elastic.get("rejoin") or {}
    floor = elastic.get("floor") or {}
    fp = elastic.get("footprint") or {}
    bits = [f"world cycle {cycle}" if cycle else "no resize recorded",
            f"{len(resizes)} resize event(s)"]
    if rejoin:
        verdict = ("bit-identical" if rejoin.get("replica_variants") == 1
                   else f"{rejoin.get('replica_variants')} variants")
        bits.append(
            f"rejoin barrier: {rejoin.get('barrier_repairs', '?')} "
            f"repair(s) for {rejoin.get('rejoins', '?')} rejoin(s), "
            f"replicas {verdict} "
            f"(fingerprint {rejoin.get('fingerprint_bytes', '?')} B)")
    if floor:
        met = "met" if floor.get("met") else "MISSED"
        bits.append(f"convergence floor {met} "
                    f"(final loss {_fmt(floor.get('final_loss'), 4)} vs "
                    f"floor {_fmt(floor.get('floor'), 2)})")
    if fp:
        ok = all(bool(v) for v in fp.values())
        bits.append("re-shard footprint vs flow pass 7 model: "
                    + ("matches at "
                       + ", ".join(f"W={k}" for k in sorted(fp))
                       if ok else f"MISMATCH {fp}"))
    return [
        "Elastic training (graft-elastic): `chaos_smoke --elastic` → "
        + ", ".join(bits)
        + f" (`ELASTIC_LAST.json`{', ' + when if when else ''})."]


def _sec_region(docs):
    region = docs("REGION_LAST.json")
    if not (isinstance(region, dict)
            and region.get("tool") == "chaos_smoke"):
        return []
    when = (region.get("captured_at") or "").split("T")[0]
    cycle = " → ".join(str(w) for w in (region.get("world_cycle") or []))
    drain = region.get("drain") or {}
    rejoin = region.get("rejoin") or {}
    floor = region.get("floor") or {}
    fp = region.get("footprint") or {}
    layout = (f"{region.get('regions', '?')} regions × "
              f"region {region.get('region_size', '?')} / "
              f"slice {region.get('slice_size', '?')}")
    bits = [f"world cycle {cycle} ({layout})"]
    if drain:
        scoped = ("region-wide" if drain.get("region_wide")
                  else f"PARTIAL scope {drain.get('scope')}")
        bits.append(
            f"{drain.get('transitions', '?')} drain transition(s) for "
            f"drift on ranks {region.get('drift_ranks')} — {scoped}, "
            f"{drain.get('drain_timeouts', 0)} watchdog timeout(s)")
    if rejoin:
        verdict = ("bit-identical" if rejoin.get("replica_variants") == 1
                   else f"{rejoin.get('replica_variants')} variants")
        bits.append(
            f"region rejoin barrier: {rejoin.get('barrier_repairs', '?')}"
            f" repair(s) for {rejoin.get('rejoins', '?')} region "
            f"rejoin(s) ({rejoin.get('rejoined_ranks', '?')} ranks), "
            f"replicas {verdict}")
    if floor:
        met = "met" if floor.get("met") else "MISSED"
        bits.append(f"convergence floor {met} "
                    f"(final loss {_fmt(floor.get('final_loss'), 4)} vs "
                    f"floor {_fmt(floor.get('floor'), 2)})")
    if fp:
        ok = all(bool(v) for v in fp.values())
        bits.append("re-shard footprint vs flow pass 7 model: "
                    + ("matches at "
                       + ", ".join(f"W={k}" for k in sorted(fp))
                       if ok else f"MISMATCH {fp}"))
    if region.get("guard_silent") is not None:
        bits.append("guard "
                    + ("silent through the drift phase"
                       if region.get("guard_silent") else "TRIPPED"))
    return [
        "Cross-region elasticity (graft-region): `chaos_smoke "
        "--region` → " + ", ".join(bits)
        + f" (`REGION_LAST.json`{', ' + when if when else ''})."]


def _sec_adapt(docs):
    adapt = docs("ADAPT_LAST.json")
    if not (isinstance(adapt, dict)
            and adapt.get("tool") == "chaos_smoke"):
        return []
    when = (adapt.get("captured_at") or "").split("T")[0]
    ti = adapt.get("tighten") or {}
    lo = adapt.get("loosen") or {}
    within = "within one window" if ti.get("within_one_window") \
        else "LATE (outside one window)"
    order = ("adapt_tighten precedes the first guard event"
             if adapt.get("ordering_ok")
             else "ORDERING VIOLATED (guard fired first)")
    bits = [
        f"{len(adapt.get('ladder') or [])}-rung ladder, window "
        f"{adapt.get('window', '?')} steps",
        f"drift → {ti.get('count', '?')} tighten(s), first at step "
        f"{ti.get('first_step', '?')} ({within})",
        f"quiet → {lo.get('count', '?')} loosen(s)",
        f"NaN → {adapt.get('guard_skips', '?')} guard skip(s), "
        f"{adapt.get('escalations', '?')} escalate-and-hold(s)",
        order,
    ]
    return [
        "Adaptive compression (graft-adapt): `chaos_smoke --adapt` → "
        + ", ".join(bits)
        + f" (`ADAPT_LAST.json`{', ' + when if when else ''})."]


def _sec_retune(docs):
    retune = docs("RETUNE_LAST.json")
    if not (isinstance(retune, dict)
            and retune.get("tool") == "chaos_smoke"):
        return []
    when = (retune.get("captured_at") or "").split("T")[0]
    drift = retune.get("drift") or {}
    fwd = retune.get("forward_promotion") or {}
    sab = retune.get("sabotage") or {}
    funnel = retune.get("funnel") or {}
    mig = fwd.get("migration") or {}
    mem = mig.get("mem") or {}
    comp = mig.get("comp") or {}
    bits = [
        f"{retune.get('incumbent', '?')} → {retune.get('candidate', '?')} "
        f"over window {retune.get('window', '?')} steps",
        f"drift verdict at step {drift.get('verdict_step', '?')} "
        f"(onset {drift.get('from_step', '?')})",
    ]
    if funnel:
        bits.append(f"re-tune funnel winner `{funnel.get('winner', '?')}` "
                    f"({len(funnel.get('measured') or [])} measured, "
                    f"{len(funnel.get('skipped') or [])} skipped)")
    if fwd:
        variants = ("bit-identical"
                    if fwd.get("replica_variants") == 1
                    else f"{fwd.get('replica_variants')} variants")
        bits.append(
            f"two-phase promotion at step {fwd.get('step', '?')} "
            f"(state migration carried {mem.get('carried', 0)}+"
            f"{comp.get('carried', 0)} / overlap "
            f"{mem.get('overlap', 0)}+{comp.get('overlap', 0)} / "
            f"fresh {mem.get('fresh', 0)}+{comp.get('fresh', 0)}, "
            f"replicas {variants})")
    if sab:
        within = ("inside probation" if sab.get("within_probation")
                  else "OUTSIDE probation")
        bit = ("bit-exact" if sab.get("bit_exact")
               else "NOT bit-exact" if sab.get("restored")
               else "NOT restored")
        bits.append(
            f"sabotaged promote → `{sab.get('trigger', '?')}` at step "
            f"{sab.get('trigger_step', '?')} ({within}), demotion to "
            f"last-known-good {bit}")
    order = ("drift→prepare→promote→clear ordering holds"
             if retune.get("ordering_ok")
             else "ORDERING VIOLATED")
    bits.append(order)
    return [
        "Online re-tuning (graft-retune): `chaos_smoke --retune` → "
        + ", ".join(bits)
        + f" (`RETUNE_LAST.json`{', ' + when if when else ''})."]


def _sec_watch(docs):
    watch = docs("WATCH_LAST.json")
    if not (isinstance(watch, dict)
            and watch.get("tool") == "graft_watch"):
        return []
    when = (watch.get("captured_at") or "").split("T")[0]
    counts = watch.get("kind_counts") or {}
    bits = [f"{watch.get('events', '?')} events "
            f"({', '.join(f'{k} {v}' for k, v in sorted(counts.items()))})",
            f"{watch.get('anomalies', 0)} anomaly record(s)"]
    ranks = watch.get("anomalous_ranks")
    if ranks:
        bits.append(f"anomalous rank(s) {ranks} first flagged at step "
                    f"{watch.get('first_anomaly_step')}")
    regr = watch.get("regressions")
    if regr is not None:
        bits.append(f"{len(regr)} baseline regression(s)")
    note = (" — seeded single-rank drift scenario, not a healthy run"
            if ranks else "")
    return [
        f"Run health (graft-watch): `graft_watch "
        f"{watch.get('artifact', '?')}` → " + ", ".join(bits) +
        f" (`WATCH_LAST.json`{', ' + when if when else ''}){note}."]


def _sec_tune(docs):
    tune = docs("TUNE_LAST.json")
    if not (isinstance(tune, dict) and tune.get("tool") == "graft_tune"):
        return []
    when = (tune.get("captured_at") or "").split("T")[0]
    bits = []
    for label, st in sorted((tune.get("static") or {}).items()):
        c = st.get("counts") or {}
        top = (st.get("ranking") or [{}])[0].get("candidate", "?")
        bits.append(
            f"{label}: {c.get('enumerated', '?')} enumerated → "
            f"{c.get('capability_rejected', 0)} capability / "
            f"{c.get('numeric_rejected', 0)} numeric / "
            f"{c.get('degradation_rejected', 0)} degradation rejected "
            f"→ {c.get('shortlisted', 0)} shortlisted, "
            f"top static pick `{top}`")
    w = tune.get("winner")
    if w:
        s = w.get("overlap_sandwich") or {}
        m = w.get("measured") or {}
        verdict = "holds" if s.get("holds") else "VIOLATED"
        bits.append(
            f"winner `{w.get('candidate')}` at {tune.get('target')} "
            f"(measured step {m.get('measured_step_ms', '?')} ms, "
            f"×{m.get('measured_speedup_vs_dense', '?')} vs dense "
            f"same-session; measured≤static overlap sandwich "
            f"{s.get('measured_overlap')}≤"
            f"{s.get('static_overlap_bound')}: {verdict}) — load with "
            f"`grace_from_params(TUNE_LAST.winner.grace_params)`")
    elif tune.get("static_only"):
        bits.append("static-only survey (no measured winner stamped)")
    platform = (tune.get("provenance") or {}).get("platform")
    note = (" — CPU-mesh pipeline evidence, not a chip capture"
            if platform and platform != "tpu" else "")
    return [
        "Autotuning (graft-tune): `graft_tune` → " + "; ".join(bits)
        + f" (`TUNE_LAST.json`{', ' + when if when else ''}){note}."]


# Dispatch: capture basename → dedicated reader, in render order. The
# None-keyed entries are views, not captures of their own (the projection
# table reads the headline doc; curve TSVs self-describe).
_SECTIONS = (
    ("BENCH_TPU_LAST.json", _sec_headline),
    ("BENCH_ALL_TPU_LAST.json", _sec_sweep),
    ("TPU_VARIANTS.jsonl", _sec_variants),
    ("BENCH_BERT_TPU_LAST.json", _sec_bert),
    (None, _sec_projection),
    (None, lambda docs: _curve_table()),
    ("BENCH_ALL_CPU.json", _sec_cpu),
    ("LINT_LAST.json", _sec_lint),
    ("PROF_LAST.json", _sec_prof),
    ("ELASTIC_LAST.json", _sec_elastic),
    ("REGION_LAST.json", _sec_region),
    ("ADAPT_LAST.json", _sec_adapt),
    ("RETUNE_LAST.json", _sec_retune),
    ("WATCH_LAST.json", _sec_watch),
    ("TUNE_LAST.json", _sec_tune),
)


def _generic_section(base, recs):
    """Ledger-driven fallback: a capture attested in the ledger but with
    no dedicated reader above still renders — ids, metric, claim class
    and provenance straight from its records."""
    out = [f"**`{base}`** (from the evidence ledger — no dedicated "
           "reader)", "",
           "| ledger id | metric | value | class | platform | devices |"
           " captured |", "|---|---|---|---|---|---|---|"]
    for r in recs:
        when = (r.get("timestamp") or "").split("T")[0]
        out.append(
            f"| `{r.get('id')}` | {r.get('metric', '?')} | "
            f"{r.get('value')} | {r.get('claim_class', '?')} | "
            f"{r.get('platform') or '—'} | {r.get('n_devices') or '—'} | "
            f"{when or '—'} |")
    return out


def _incident_rollup(latest):
    """Flight-recorder roll-up: ledger records minted by the incident
    recorder plus whatever sits under EVIDENCE/incidents/."""
    import glob
    incs = [r for r in latest.values()
            if r.get("tool") == "flight_recorder"]
    files = glob.glob(os.path.join(ROOT, "EVIDENCE", "incidents",
                                   "*.json"))
    if not incs and not files:
        return []
    return [f"Flight recorder: {len(files)} incident record(s) under "
            f"`EVIDENCE/incidents/` ({len(incs)} ledger-attached) — each "
            "snapshots the telemetry ring, watch timeline, adapt rung "
            "history and profiler attribution at its trigger step."]


def build() -> str:
    cache = {}

    def docs(name):
        if name not in cache:
            cache[name] = _load(name)
        return cache[name]

    by_capture, latest = _ledger_view()
    parts = []
    covered = set()
    for base, render in _SECTIONS:
        if base is not None:
            covered.add(base)
        lines = render(docs)
        if not lines:
            continue
        parts += lines
        if base is not None:
            parts += _ledger_note(by_capture.get(base) or [])
        parts.append("")
    # Ledger captures nobody above reads: generic render. Incident
    # records roll up as one line rather than one section per file.
    extras = sorted(base for base, recs in by_capture.items()
                    if base not in covered
                    and not all(r.get("tool") == "flight_recorder"
                                for r in recs))
    for base in extras:
        parts += _generic_section(base, by_capture[base])
        parts.append("")
    parts += _incident_rollup(latest)
    return "\n".join(parts).rstrip() + "\n"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--update-readme", action="store_true")
    args = ap.parse_args()
    md = build()
    if not args.update_readme:
        print(md, end="")
        return
    path = os.path.join(ROOT, "README.md")
    with open(path) as f:
        text = f.read()
    if BEGIN not in text or END not in text:
        raise SystemExit(f"README.md lacks {BEGIN} / {END} markers")
    pre = text.split(BEGIN)[0]
    post = text.split(END)[1]
    with open(path, "w") as f:
        f.write(pre + BEGIN + "\n" + md + END + post)
    print("README.md updated")


if __name__ == "__main__":
    main()
