"""The serve windows recorded on a TPU v5e with their step's compiled text
(``record_scopes.py``, N=4096, K=16): ``data/scoped_chunkae_q8_k16`` (the
chunked-AE q8 codec, kernels on) and ``data/scoped_fcae_k16`` (the FC AE).

Off the chip, the scope reader (``bench/scopes.py``) takes a serve step's
text from the recording of the same configuration and shape: a CPU compile
names its instructions otherwise than the chip's, so the ops of a chip
trace would find no scope. Every other cell's programs are compiled as on
the chip."""
import gzip
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import scopes, trace  # noqa: E402
from bench.drivers import serve  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
# configuration → recording
RECORDINGS = {"cifar_chunkae_q8": "scoped_chunkae_q8_k16",
              "cifar_fcae": "scoped_fcae_k16"}
POPULATION, BUFFER_K = 4096, 16


def recorded_text(config: str) -> str:
    with gzip.open(os.path.join(DATA, RECORDINGS[config] + ".hlo.txt.gz"),
                   "rt") as f:
        return f.read()


@pytest.fixture(autouse=True)
def recorded_program_texts(monkeypatch):
    import jax
    monkeypatch.setattr(scopes, "_MAPS", {})
    if jax.devices()[0].platform == "tpu":
        return
    compiled = scopes.program_texts

    def program_texts(cell):
        config = cell.config["name"]
        if (config in RECORDINGS
                and cell.traffic["population"] == POPULATION
                and cell.traffic["buffer_k"] == BUFFER_K):
            return {serve.PROGRAMS[0]: recorded_text(config)}
        return compiled(cell)

    monkeypatch.setattr(scopes, "program_texts", program_texts)


@pytest.fixture(scope="module", params=sorted(RECORDINGS))
def recording(request, tmp_path_factory):
    """One recording: its configuration, step text, trace and reduction."""
    config = request.param
    path = tmp_path_factory.mktemp("trace") / "scoped.xplane.pb"
    with gzip.open(os.path.join(DATA, RECORDINGS[config] + ".xplane.pb.gz"),
                   "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    tr = trace.load(str(path))
    return {"config": config, "text": recorded_text(config),
            "path": str(path), "trace": tr, "summary": trace.summarize(tr)}
