"""`python -m repro.api` — declarative experiment launcher.

Three input sources, later ones winning:

  1. defaults (``ExperimentConfig()``)
  2. ``--config exp.json`` — a saved config file
  3. flat dotted overrides: ``--train.steps=5 --graft.eps=0.3``
     (``--graft=none`` disables selection; values are JSON, falling back
     to strings). ``--data.source=<name>`` swaps the training workload to
     any registered task/data source (``repro.data.sources``) — put
     model/train overrides BEFORE it, per-source ``--data.field=value``
     overrides after.

``--resume DIR`` ignores all of the above and reconstructs the experiment
from the manifest embedded in ``DIR``'s latest checkpoint.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from repro.api.config import ExperimentConfig
from repro.api.trainer import Trainer


# <checkout>/.jax_cache: a fixed path, since the path is part of the
# persistent cache's key and a moving directory never hits
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else at the checkout's
    ``.jax_cache``. Only entry points call this; library code sets no
    cache. Returns the directory."""
    import jax
    path = os.path.normpath(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                            or _DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _split_args(argv: List[str]):
    """Separate known flags from --section.field=value overrides."""
    ap = argparse.ArgumentParser(
        prog="python -m repro.api",
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", default=None,
                    help="path to an ExperimentConfig JSON file")
    ap.add_argument("--resume", default=None, metavar="CKPT_DIR",
                    help="resume from a checkpoint directory's embedded config")
    ap.add_argument("--dump-config", action="store_true",
                    help="print the finalized config JSON and exit (no training)")
    args, rest = ap.parse_known_args(argv)
    overrides = []
    for tok in rest:
        if tok.startswith("--") and "=" in tok:
            overrides.append(tok[2:])
        else:
            ap.error(f"unrecognized argument '{tok}' "
                     "(overrides use --section.field=value)")
    return args, overrides


def main(argv: Optional[List[str]] = None) -> int:
    args, overrides = _split_args(sys.argv[1:] if argv is None else argv)

    if args.resume:
        if overrides or args.config:
            print("error: --resume reconstructs the experiment from the "
                  "manifest alone; drop the other flags", file=sys.stderr)
            return 2
        trainer = Trainer.from_checkpoint(args.resume)
        if args.dump_config:
            print(trainer.config.to_json(indent=1))
            return 0
    else:
        cfg = (ExperimentConfig.load(args.config) if args.config
               else ExperimentConfig())
        cfg = cfg.apply_overrides(overrides)
        if args.dump_config:
            print(cfg.finalized().to_json(indent=1))
            return 0
        trainer = Trainer(cfg)

    report = trainer.fit()
    print(json.dumps({k: v for k, v in report.items() if k != "history"},
                     indent=1))
    return 0
