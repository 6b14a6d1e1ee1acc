"""Docs-drift guard: every CLI example in README.md must parse.

Extracts ``python -m tpu_hc_bench ...`` invocations from README code
blocks and runs them through the real positional-arg splitter and flag
parser (no execution) — a README example with a stale flag or model name
fails here instead of on a user's terminal.
"""

import re
from pathlib import Path

import pytest

from tpu_hc_bench import flags, launcher
from tpu_hc_bench.models import get_model_spec

README = Path(__file__).resolve().parent.parent / "README.md"


def _example_argvs():
    text = README.read_text()
    # join backslash-continued lines, then walk fenced code blocks only
    text = re.sub(r"\\\n\s*", " ", text)
    argvs = []
    in_block = False
    for line in text.splitlines():
        if line.strip().startswith("```"):
            in_block = not in_block
            continue
        if not in_block:
            continue
        line = line.split("#")[0].strip()
        m = re.match(r"python -m tpu_hc_bench\s+(.+)", line)
        if m:
            argvs.append(m.group(1).split())
    assert argvs, "no CLI examples found in README"
    return argvs


@pytest.mark.parametrize("argv", _example_argvs(),
                         ids=lambda a: " ".join(a)[:60])
def test_readme_cli_example_parses(argv):
    from tpu_hc_bench.parallel.fabric import resolve_fabric

    if argv and argv[0] == "fleet":
        # the fleet subcommand (round 19) has its own argparse surface
        from tpu_hc_bench.fleet.__main__ import build_parser

        build_parser().parse_args(argv[1:])
        return
    pos, rest = launcher.parse_positionals(argv)
    assert len(pos) in (0, 4), f"positional contract violated: {pos}"
    cfg = flags.parse_flags(rest)
    get_model_spec(cfg.model)          # model name must exist in the zoo
    if pos:
        resolve_fabric(pos[3])         # the launcher's own validator


@pytest.mark.slow
def test_chip_smoke_refuses_without_a_chip(tmp_path):
    """chip_smoke.py is the proof the system starts ON THE CHIP: with
    none it exits non-zero and prints no result line (no CPU fallback)."""
    import os
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, str(README.parent / "chip_smoke.py"), "--out",
         str(tmp_path)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "refusing to fall back" in proc.stdout
    assert '"ok"' not in proc.stdout
