"""Unified job CLI: ``python -m repro <command> --config job.json``.

    python -m repro train  --config experiments/jobs/paper_echo_cgc.json \
        --set train.steps=3
    python -m repro serve  --config experiments/jobs/serve_smoke.json
    python -m repro dryrun --config job.json --set dryrun.shape=train_4k
    python -m repro bench  --config job.json
    python -m repro report experiments/runs/<run_dir>   # render a run
    python -m repro list                     # registered plugins
    python -m repro show   --config job.json [--set ...]   # resolved JSON

Every command loads one :class:`repro.run.RunConfig`, applies the
dotted-path ``--set key.path=value`` overrides, and calls the matching
``repro.run`` facade. Legacy flag CLIs (``python -m repro.launch.train``
etc.) keep working as deprecation shims over the same facades.

This module must stay import-light until the command is known: dryrun
forces 512 fake host devices at import time, which only works before jax
initialises its backend.
"""
from __future__ import annotations

import argparse
import sys


def _add_job_args(sub: argparse.ArgumentParser) -> None:
    sub.add_argument("--config", required=True,
                     help="path to a RunConfig job JSON")
    sub.add_argument("--set", dest="overrides", action="append",
                     default=[], metavar="KEY.PATH=VALUE",
                     help="dotted-path override, e.g. train.steps=3 "
                          "(repeatable)")


def _load(args) -> "object":
    from repro.run import RunConfig, apply_overrides
    cfg = RunConfig.load(args.config)
    return apply_overrides(cfg, args.overrides)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro",
        description="Declarative job runner over repro.run configs")
    sub = ap.add_subparsers(dest="command", required=True)
    for name, doc in (("train", "run the training workload"),
                      ("serve", "run the serving workload"),
                      ("dryrun", "lower+compile on the production mesh"),
                      ("bench", "serving benchmark (continuous vs fixed)"),
                      ("show", "print the resolved job config JSON")):
        _add_job_args(sub.add_parser(name, help=doc))
    sub.add_parser("list", help="print every registered plugin per kind")
    rep = sub.add_parser("report",
                         help="render a finished run dir's summary "
                              "(throughput, echo rate, bits, spans)")
    rep.add_argument("run_dir", help="a run directory containing "
                                     "summary.json")
    args = ap.parse_args(argv)

    if args.command == "list":
        from repro.run import available
        for kind, names in available().items():
            print(f"{kind}: {', '.join(names)}")
        return 0

    if args.command == "report":
        # stdlib-only path: reporting never initialises jax
        from repro.obs import report as render_report
        try:
            render_report(args.run_dir)
        except (OSError, ValueError, KeyError) as e:
            raise SystemExit(f"error: {e}") from None
        return 0

    if args.command == "dryrun":
        # MUST precede any jax-initialising import: this sets the forced
        # 512-device topology dryrun compiles against.
        import repro.launch.dryrun  # noqa: F401

    try:
        cfg = _load(args)
    except (ValueError, OSError) as e:
        raise SystemExit(f"error: {e}") from None
    if args.command == "show":
        print(cfg.to_json())
        return 0

    from repro.run import facade
    facade.enable_compile_cache()
    try:
        if args.command == "train":
            facade.print_train_summary(facade.train(cfg))
        elif args.command == "serve":
            facade.print_serve_summary(facade.serve(cfg))
        elif args.command == "dryrun":
            res = facade.dryrun(cfg)
            print(f"[{res.summary.get('status', '?')}] record -> "
                  f"{res.record_path}")
            return 0 if res.summary.get("status") in ("ok", "skipped",
                                                      "lowered") else 1
        elif args.command == "bench":
            res = facade.bench(cfg)
            print(f"continuous/fixed tokens/s: {res.speedup:.2f}x "
                  f"(result -> {res.run_dir}/result.json)")
    except ValueError as e:
        raise SystemExit(f"error: {e}") from None
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
