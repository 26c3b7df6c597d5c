"""Fetch the real parity datasets (VERDICT r3 #6).

Downloads MovieLens-100K and UCI covtype into data/real/ with checksum
verification. This build environment has **no network egress**, so
tools/train_benchmark.py's quality numbers come from dataset-shaped
synthetics and say so; run this script on a connected
host, then `python tools/real_data_eval.py` to produce the real-data
parity table.

Usage:
    python tools/fetch_datasets.py [--dest data/real]
"""

from __future__ import annotations

import argparse
import gzip
import hashlib
import io
import shutil
import sys
import urllib.request
import zipfile
from pathlib import Path

ML100K_URL = "https://files.grouplens.org/datasets/movielens/ml-100k.zip"
ML100K_SHA256 = "0e33842e24a9c977be4e0107933c0723889861041a05498981c6b9ca8d93dee1"
COVTYPE_URL = (
    "https://archive.ics.uci.edu/ml/machine-learning-databases/covtype/covtype.data.gz"
)
# The UCI mirror serves stable bytes; figshare (sklearn's mirror) also works.
COVTYPE_SHA256 = "614360d0257557dd1792834a85a1cdebfadc3c4f30b011d56afee7ffb5b15771"


def _download(url: str, sha256: str | None) -> bytes:
    print(f"fetching {url} ...", flush=True)
    with urllib.request.urlopen(url, timeout=120) as r:
        data = r.read()
    digest = hashlib.sha256(data).hexdigest()
    if sha256 and digest != sha256:
        sys.exit(f"checksum mismatch for {url}: got {digest}, want {sha256}")
    return data


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dest", default="data/real")
    args = ap.parse_args()
    dest = Path(args.dest)
    dest.mkdir(parents=True, exist_ok=True)

    ml_dir = dest / "ml-100k"
    if not (ml_dir / "u.data").exists():
        blob = _download(ML100K_URL, ML100K_SHA256)
        with zipfile.ZipFile(io.BytesIO(blob)) as z:
            z.extractall(dest)
        print(f"ml-100k -> {ml_dir}")
    else:
        print("ml-100k already present")

    cov = dest / "covtype.data"
    if not cov.exists():
        blob = _download(COVTYPE_URL, COVTYPE_SHA256)
        with gzip.open(io.BytesIO(blob)) as f, open(cov, "wb") as out:
            shutil.copyfileobj(f, out)
        print(f"covtype -> {cov}")
    else:
        print("covtype already present")


if __name__ == "__main__":
    main()
