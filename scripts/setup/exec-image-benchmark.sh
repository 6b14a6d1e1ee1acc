#!/usr/bin/env bash
# The `singularity exec` leg of the L2 contract: run a benchmark FROM the
# built image, not host python.  The reference wraps every rank in
# `singularity exec <sif> ...` (run-tf-sing-ucx-openmpi.sh:107); our image
# form is the relocatable venv tarball from build-venv-image.sh, so the
# analog is: unpack the tarball to a FRESH prefix (proving relocation, not
# just the build venv working in place) and run the literal 4-positional
# CLI with the image's own interpreter.
#
#   usage: ./exec-image-benchmark.sh <tarball> [out_dir] [-- extra args...]
#
# The image's own libtpu drives the local chips; nothing from the host's
# python stack is bound in.
#
# Defaults to the reference's literal single-node config `1 0 64 ici`
# (README.md:68-73 analog) on a short protocol; pass extra args after --
# to override.  Writes the full transcript + the result line to
# <out_dir>/exec-rehearsal.txt.  A missing throughput line fails loudly.
set -euo pipefail

TARBALL="${1:?usage: exec-image-benchmark.sh <tarball> [out_dir] [-- args]}"
shift
OUT="$(dirname "$TARBALL")"
case "${1:-}" in
  --) ;;                          # no out_dir given, args follow
  -*) echo "error: flags must follow a literal -- separator" >&2
      exit 2 ;;                   # not silently an out_dir named "-x..."
  ?*) OUT="$1"; shift ;;
esac
if [ "${1:-}" = "--" ]; then shift; fi
EXTRA=("$@")
[ ${#EXTRA[@]} -gt 0 ] || EXTRA=(--num_warmup_batches=10 --num_batches=30)

PREFIX="$(mktemp -d /tmp/tpu-hc-image-exec.XXXXXX)"
trap 'rm -rf "$PREFIX"' EXIT
mkdir -p "$OUT"
REC="$OUT/exec-rehearsal.txt"

{
  echo "== exec-image-benchmark $(date -u +%Y-%m-%dT%H:%M:%SZ) =="
  echo "image: $TARBALL ($(du -h "$TARBALL" | cut -f1))"
  echo "sha256: $(sha256sum "$TARBALL" | cut -d' ' -f1)"
  echo "fresh prefix: $PREFIX"
  tar -C "$PREFIX" -xzf "$TARBALL"
  PY="$PREFIX/venv/bin/python"
  echo "image python: $($PY --version 2>&1)"
  # no host PYTHONPATH, no repo cwd: everything must come from the image
  echo "+ $PY -m tpu_hc_bench 1 0 64 ici ${EXTRA[*]}"
  ( cd "$PREFIX" && env -u PYTHONPATH "$PY" -m tpu_hc_bench \
      1 0 64 ici "${EXTRA[@]}" )
  echo "== exec OK =="
} 2>&1 | tee "$REC"

# image members print "total images/sec", text/CTC/integer members
# "total examples/sec" (driver _example_units) — accept either
grep -Eq "total (images|examples)/sec" "$REC" || {
  echo "FAIL: no throughput line in $REC" >&2; exit 1; }
