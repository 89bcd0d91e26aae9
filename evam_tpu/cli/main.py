"""evam-tpu command line: serve / fetch-models / bench / list.

The single CLI replacing the reference's RUN_MODE shell dispatch
(reference run.sh:26-30): ``serve`` starts the REST (EVA-equivalent)
or msgbus (EII-equivalent) frontend per settings; ``fetch-models``
is the model_downloader counterpart (reference
tools/model_downloader/model_downloader.sh:24-32).
"""

from __future__ import annotations

import argparse
import json
import sys

from evam_tpu.config import get_settings
from evam_tpu.obs import configure_logging, get_logger

log = get_logger("cli")


def cmd_list(args) -> int:
    from evam_tpu.graph import PipelineLoader
    from evam_tpu.models import ModelRegistry

    settings = get_settings()
    loader = PipelineLoader(settings.pipelines_dir)
    print(json.dumps(
        {
            "pipelines": [f"{n}/{v}" for n, v in loader.names()],
            "models": ModelRegistry(settings.models_dir).keys(),
        },
        indent=2,
    ))
    return 0


def cmd_fetch_models(args) -> int:
    modes = [m for m, on in [("--download", args.download),
                             ("--from-ir", bool(args.from_ir)),
                             ("--synthesize-omz", bool(args.synthesize_omz)),
                             ("--synthesize-lm", bool(args.synthesize_lm))]
             if on]
    if len(modes) > 1:
        print(f"fetch-models: {' and '.join(modes)} are mutually "
              "exclusive", file=sys.stderr)
        return 2
    if args.download:
        from evam_tpu.models import download as dl

        try:
            report = dl.download_models(
                model_list=args.model_list, output=args.output,
                base_url=args.base_url or dl.DEFAULT_BASE_URL,
                proc_base_url=args.proc_base_url or dl.DEFAULT_PROC_BASE_URL,
                force=args.force,
            )
        except dl.DownloadError as exc:
            print(f"fetch-models --download: {exc}", file=sys.stderr)
            return 1
        print(f"installed={report.installed} skipped={report.skipped} "
              f"failed={report.failed}")
        return 0 if report.ok else 1
    if args.synthesize_lm:
        from evam_tpu.models.fetch import synthesize_lm

        return synthesize_lm(args.output, alias=args.synthesize_lm,
                             version=args.version, preset=args.lm_preset)
    if args.synthesize_omz:
        from evam_tpu.models.fetch import synthesize_omz

        return synthesize_omz(
            args.output, alias=args.synthesize_omz, version=args.version,
            precision=args.precision, input_size=args.size,
            topology=args.topology,
        )
    if args.from_ir:
        from evam_tpu.models.fetch import import_ir_dir

        return import_ir_dir(
            args.from_ir, args.output,
            alias=args.alias, version=args.version, precision=args.precision,
        )
    from evam_tpu.models.fetch import fetch_models

    return fetch_models(
        model_list=args.model_list, output=args.output, force=args.force
    )


def cmd_serve(args) -> int:
    from evam_tpu.parallel.mesh import require_requested_backend

    require_requested_backend()
    settings = get_settings()
    mode = (args.mode or settings.run_mode).upper()
    if mode == "EII":
        from evam_tpu.eii.manager import run_eii_service

        return run_eii_service(settings)
    from evam_tpu.server.app import run_server

    return run_server(settings)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="evam-tpu")
    sub = p.add_subparsers(dest="command", required=True)

    s = sub.add_parser("serve", help="start the serving frontend")
    s.add_argument("--mode", choices=["EVA", "EII", "eva", "eii"], default=None)
    s.set_defaults(fn=cmd_serve)

    f = sub.add_parser("fetch-models", help="materialize the model directory")
    f.add_argument("--model-list", default="models_list/models.list.yml")
    f.add_argument("--output", default="models")
    f.add_argument("--force", action="store_true")
    f.add_argument("--download", action="store_true",
                   help="fetch OpenVINO IR artifacts + model-procs over "
                        "the network (reference model_downloader "
                        "counterpart); validates the model list with "
                        "jsonschema, import-checks every IR before "
                        "declaring it installed")
    f.add_argument("--base-url", default=None,
                   help="--download: IR artifact root "
                        "({base}/{model}/{precision}/{model}.xml)")
    f.add_argument("--proc-base-url", default=None,
                   help="--download: model-proc root "
                        "({base}/{model}.json)")
    f.add_argument("--from-ir", default=None, metavar="DIR",
                   help="install OpenVINO IR .xml/.bin (file or tree) "
                        "into the serving layout instead of zoo export")
    f.add_argument("--alias", default=None,
                   help="serving alias for --from-ir (default: xml stem)")
    f.add_argument("--synthesize-omz", default=None, metavar="ALIAS",
                   help="materialize an OMZ-topology-shaped MobileNet-SSD "
                        "IR under ALIAS (offline stand-in for the OMZ "
                        "download; see models/ir_build.py)")
    f.add_argument("--synthesize-lm", default=None, metavar="ALIAS",
                   help="install a language model for the describe stage "
                        "under ALIAS/--version as its config file; the "
                        "weights are made on the device from its seed")
    f.add_argument("--lm-preset", default="deepseek_v2_ep8",
                   help="--synthesize-lm: which config of "
                        "models/lm/presets.py (deepseek_v2_ep8, jamba2_3b, "
                        "kimi_linear_ep4, lfm2_moe_ep2, laguna_xs2_pp8, "
                        "nemotron3_super_ep8, brumby_14b_pp8, and a "
                        "tiny one of each family)")
    f.add_argument("--size", type=int, default=None,
                   help="input resolution for --synthesize-omz "
                        "(default: 512 for ssd, 72 for attributes)")
    f.add_argument("--topology",
                   choices=["ssd", "attributes", "manifest"],
                   default="ssd",
                   help="--synthesize-omz topology: MobileNet-SSD "
                        "detector, multi-head attributes classifier, "
                        "or 'manifest' = IR-backed stand-ins for ALL "
                        "8 reference-manifest models (ALIAS ignored)")
    f.add_argument("--version", default="1")
    f.add_argument("--precision", default="FP32")
    f.set_defaults(fn=cmd_fetch_models)

    ls = sub.add_parser("list", help="list pipelines and models")
    ls.set_defaults(fn=cmd_list)
    return p


def main(argv: list[str] | None = None) -> int:
    configure_logging()
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
