"""``python -m repro report <run_dir>`` — render a finished run.

Reads the ``summary.json`` the facades drop at the end of every run
(``{"kind", "summary", "obs": {counters, spans}}``) plus the
run dir's ``config.json``, and prints a human-readable digest:
throughput, echo rate, bits-vs-baseline, and the per-subsystem span
breakdown. Stdlib-only so reporting never imports jax.
"""
from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional


def load_run(run_dir: str) -> Dict[str, Any]:
    """Load ``summary.json`` (+ ``config.json`` if present); raises a
    FileNotFoundError naming what a finished run should contain."""
    path = os.path.join(run_dir, "summary.json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{path} not found — is {run_dir!r} a finished run dir? "
            f"(runs write summary.json on completion)")
    with open(path) as fh:
        data = json.load(fh)
    cfg_path = os.path.join(run_dir, "config.json")
    if os.path.exists(cfg_path):
        with open(cfg_path) as fh:
            data.setdefault("config", json.load(fh))
    data.setdefault("policy_events", load_policy_events(run_dir))
    data.setdefault("net_events", load_net_events(run_dir))
    return data


def _load_events(run_dir: str, prefix: str) -> List[Dict[str, Any]]:
    """Events under one kind prefix from ``events.jsonl`` (empty when
    the run had no jsonl tracker). Malformed lines — e.g. a run killed
    mid-write — are skipped, not fatal."""
    path = os.path.join(run_dir, "events.jsonl")
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if str(rec.get("kind", "")).startswith(prefix):
                out.append(rec)
    return out


def load_policy_events(run_dir: str) -> List[Dict[str, Any]]:
    """The run's ``comm.policy.*`` events."""
    return _load_events(run_dir, "comm.policy.")


def load_net_events(run_dir: str) -> List[Dict[str, Any]]:
    """The run's ``net.*`` events (topology / relay channel / reliable
    broadcast digests from ``repro.net``)."""
    return _load_events(run_dir, "net.")


def _fmt_s(t: float) -> str:
    if t >= 1.0:
        return f"{t:.2f}s"
    if t >= 1e-3:
        return f"{t * 1e3:.2f}ms"
    return f"{t * 1e6:.0f}us"


def _pct(x: float) -> str:
    return f"{100.0 * x:.1f}%"


def _span_lines(spans: Dict[str, Dict[str, Any]]) -> List[str]:
    """The per-subsystem breakdown: every span path, indented by depth,
    with share-of-root-time, total, count and mean."""
    if not spans:
        return ["  (no spans recorded)"]
    root_total = sum(v["total_s"] for p, v in spans.items() if "/" not in p)
    lines = []
    width = max(len(p.rsplit("/", 1)[-1]) + 2 * p.count("/") for p in spans)
    for path in sorted(spans):
        v = spans[path]
        depth = path.count("/")
        name = "  " * depth + path.rsplit("/", 1)[-1]
        share = v["total_s"] / root_total if root_total > 0 else 0.0
        mean = v["total_s"] / v["count"] if v["count"] else 0.0
        lines.append(f"  {name:<{width}}  {_pct(share):>6}  "
                     f"total {_fmt_s(v['total_s']):>9}  "
                     f"n={v['count']:<6} mean {_fmt_s(mean)}")
    return lines


def _train_lines(s: Dict[str, Any]) -> List[str]:
    lines = []
    if "rounds" in s:
        lines.append(f"  rounds        {s['rounds']}"
                     + (f"  (wall {s['wall_s']}s)" if "wall_s" in s else ""))
    if s.get("rounds") and "wall_s" in s and s["wall_s"]:
        lines.append(f"  rounds/s      "
                     f"{s['rounds'] / s['wall_s']:.2f}")
    if "first_loss" in s and "final_loss" in s:
        lines.append(f"  loss          {s['first_loss']:.6g} -> "
                     f"{s['final_loss']:.6g}")
    if "echo_rate" in s:
        lines.append(f"  echo rounds   {s['echo_rounds']}/{s['rounds']} "
                     f"({_pct(s['echo_rate'])})")
    if "bits_sent" in s:
        lines.append(f"  bits sent     {s['bits_sent']:.4g} vs baseline "
                     f"{s['bits_baseline']:.4g} "
                     f"({_pct(s.get('bits_saving', 0.0))} saved)")
    return lines


def _serve_lines(s: Dict[str, Any]) -> List[str]:
    lines = []
    if "tokens_generated" in s:
        lines.append(f"  tokens        {s['tokens_generated']} in "
                     f"{s.get('wall_s', 0.0)}s "
                     f"({s.get('tokens_per_s', 0.0)} tok/s)")
    if "latency_p50_s" in s:
        lines.append(f"  latency       p50={s['latency_p50_s']}s "
                     f"p99={s['latency_p99_s']}s")
    if "ttft_p50_s" in s:
        lines.append(f"  ttft          p50={s['ttft_p50_s']}s "
                     f"p99={s['ttft_p99_s']}s "
                     f"itl p50={s.get('itl_p50_s', 0.0)}s")
    if "preemptions" in s:
        lines.append(f"  preemptions   {s['preemptions']}")
    if s.get("prefix_hit_tokens"):
        lines.append(f"  prefix cache  {_pct(s['prefix_hit_rate'])} hit "
                     f"({s['prefix_hit_tokens']} tokens adopted, "
                     f"{s.get('cow_copies', 0)} CoW copies)")
    return lines


def _comm_lines(s: Dict[str, Any],
                events: List[Dict[str, Any]]) -> List[str]:
    """The adaptive-communication digest: which policy ran, every
    decision it took, the codec mix, and cumulative bits against the
    fp32 all-raw baseline (the paper's cost unit)."""
    rounds = [e for e in events if e.get("kind") == "comm.policy.round"]
    decisions = [e for e in events
                 if e.get("kind") == "comm.policy.decision"]
    if not rounds and not decisions and "policy" not in s:
        return []
    lines = []
    policy = s.get("policy") or next(
        (e["policy"] for e in decisions + rounds if e.get("policy")), "?")
    lines.append(f"  policy        {policy}")
    if "codec_final" in s:
        lines.append(f"  final         codec={s['codec_final']} "
                     f"echo_r={s.get('echo_r_final')}")
    switches = s.get("codec_switches")
    if switches is None and rounds:
        switches = sum(1 for a, b in zip(rounds, rounds[1:])
                       if a.get("codec") != b.get("codec"))
    if switches is not None:
        lines.append(f"  codec switches {switches}")
    for e in decisions:
        lines.append(f"  decision @{e.get('step', '?'):<4} "
                     f"codec={e.get('codec')} r={e.get('echo_r')}")
    if rounds:
        tally: Dict[str, int] = {}
        for e in rounds:
            c = str(e.get("codec"))
            tally[c] = tally.get(c, 0) + 1
        lines.append("  codec rounds  "
                     + ", ".join(f"{c} x{tally[c]}" for c in sorted(tally)))
        last = rounds[-1]
        cum = last.get("bits_cumulative")
        base = last.get("fp32_baseline_cumulative")
        if cum is not None and base:
            lines.append(f"  bits          {float(cum):.4g} vs "
                         f"{float(base):.4g} fp32 all-raw "
                         f"({_pct(1.0 - float(cum) / float(base))} saved)")
    return lines


def _net_lines(events: List[Dict[str, Any]]) -> List[str]:
    """The network digest: hearing graph, relay tier, and the reliable-
    broadcast outcome (``net.*`` events from ``repro.net``)."""
    lines = []
    for e in events:
        kind = e.get("kind")
        if kind == "net.topology":
            lines.append(f"  topology      {e.get('topology')} "
                         f"(n={e.get('n')}, edges={e.get('edges')}"
                         + (", complete" if e.get("complete") else "")
                         + ")")
        elif kind == "net.channel":
            lines.append(f"  relay tier    {e.get('relays')} relays "
                         f"({e.get('byz_relays')} byzantine), "
                         f"broadcast={e.get('broadcast')}, "
                         f"{'protected' if e.get('protected') else 'UNPROTECTED'}, "
                         f"price x{e.get('price_factor')}")
        elif kind == "net.broadcast":
            lines.append(f"  broadcast     {e.get('discipline')}: "
                         f"accepted={e.get('accepted')} "
                         f"safe={e.get('safe')} "
                         f"messages={e.get('messages')}")
    return lines


def render(data: Dict[str, Any], run_dir: str = "") -> str:
    """Render a loaded run (see :func:`load_run`) to the report text."""
    kind = data.get("kind", "run")
    name = (data.get("config") or {}).get("name", "")
    obs = data.get("obs") or {}
    summary = data.get("summary") or {}

    lines = [f"== repro report: {kind}"
             + (f" '{name}'" if name else "")
             + (f" ({run_dir})" if run_dir else "") + " =="]
    body = _train_lines(summary) if kind == "train" \
        else _serve_lines(summary) if kind == "serve" else []
    if not body:   # unknown kind, or a summary with none of the keys
        body = [f"  {k:<13} {v}" for k, v in sorted(summary.items())]
    lines += body

    comm = _comm_lines(summary, data.get("policy_events") or [])
    if comm:
        lines.append("-- comm policy --")
        lines += comm

    net = _net_lines(data.get("net_events") or [])
    if net:
        lines.append("-- network --")
        lines += net

    lines.append("-- span breakdown (share of root spans) --")
    lines += _span_lines(obs.get("spans") or {})

    counters = obs.get("counters") or {}
    if counters:
        lines.append("-- counters --")
        cw = max(len(k) for k in counters)
        lines += [f"  {k:<{cw}}  {counters[k]}" for k in sorted(counters)]
    return "\n".join(lines)


def report(run_dir: str,
           printer: Optional[Callable[[str], None]] = None) -> str:
    """Load + render + print one run dir; returns the rendered text."""
    text = render(load_run(run_dir), run_dir=run_dir)
    (printer or print)(text)
    return text
