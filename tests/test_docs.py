"""The documents name only what exists.

`README.md` and every `doc/*.md` are read for three kinds of back-ticked
names, and each must be there: a file of this repository, a config key
(a field of its dataclass in `garage_tpu/utils/config.py`), an environment
variable (read somewhere in `garage_tpu/`).  A document that cites a
deleted script, a retired knob or a renamed module fails here, not in
front of an operator.
"""

import dataclasses
import functools
import glob
import os
import re

import pytest

from garage_tpu.utils import config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "doc", "*.md"))
)
ROOTS = ("garage_tpu/", "tests/", "script/", "benchmark/", "doc/")
FILE_SUFFIXES = (".py", ".json", ".md", ".sh")
SKIPPED_DIRS = {"chiprun_out", "__pycache__"}


@functools.cache
def _tree():
    """(basenames of every file, top-level packages of garage_tpu/)."""
    names = set()
    for top, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d not in SKIPPED_DIRS]
        names.update(files)
    return names, set(os.listdir(os.path.join(REPO, "garage_tpu")))


@functools.cache
def _sections():
    """config section name -> its dataclass's field names (`[tpu]` ->
    TpuConfig's)."""
    out = {}
    for f in dataclasses.fields(config.Config):
        fac = f.default_factory
        if isinstance(fac, type) and dataclasses.is_dataclass(fac):
            out[f.name] = {x.name for x in dataclasses.fields(fac)}
    out["tenants.*"] = {x.name for x in dataclasses.fields(config.TenantClassConfig)}
    return out


def _exists(path: str) -> bool:
    full = os.path.join(REPO, path)
    return bool(glob.glob(full)) if "*" in path else os.path.exists(full)


def _path_of(token: str) -> str:
    """`tests/test_x.py::test_y`, `garage_tpu/a.py:12-14`, `script/x.py --flag`
    -> the file they name."""
    word = token.split()[0].split("::")[0]
    word = re.sub(r":\d+(-\d+)?(,\d+(-\d+)?)*$", "", word)
    return word.rstrip(".,;:)")


@functools.cache
def _source() -> str:
    """Every line of `garage_tpu/`: where an environment variable or a
    digest key has to be read to count as there."""
    parts = []
    for top, _dirs, files in os.walk(os.path.join(REPO, "garage_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(top, name), encoding="utf-8") as f:
                    parts.append(f.read())
    return "\n".join(parts)


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    names, packages = _tree()
    sections = _sections()
    source = _source()
    missing = []
    for token in re.findall(r"`([^`\n]+)`", text):
        path = _path_of(token)
        if path.startswith("src/"):
            continue  # the upstream project's tree, not ours
        if path.startswith(ROOTS):
            if not _exists(path):
                missing.append(f"file {path}")
        elif "/" in path and path.split("/")[0] in packages:
            # `block/codec/ec.py`: written from inside garage_tpu/
            if path.endswith((".py", ".cpp", "/")) and not _exists("garage_tpu/" + path):
                missing.append(f"file garage_tpu/{path}")
        elif "/" not in path and path.endswith(FILE_SUFFIXES) and path[0] != ".":
            # a bare name: at the root, or some file's name in the tree
            if not (_exists(path) or path in names):
                missing.append(f"file {path}")
        m = re.match(r"^\[([a-z0-9_.]+)\]\s+([a-z0-9_*]+)", token) or re.match(
            r"^([a-z0-9_]+)\.([a-z0-9_]+)$", token
        )
        if m:
            section, key = m.groups()
            if section.startswith("tenants."):
                section = "tenants.*"
            if section in sections:
                fields = sections[section]
                ok = (
                    any(f.startswith(key[:-1]) for f in fields)
                    if key.endswith("*")
                    else key in fields
                )
                # `tpu.dps`: the telemetry digest names its sections alike
                if not ok and not (token[0] != "[" and f'"{key}"' in source):
                    missing.append(f"config key [{section}] {key}")
            elif token.startswith("["):
                missing.append(f"config section [{section}]")
    for var in sorted(set(re.findall(r"\bGARAGE_[A-Z0-9][A-Z0-9_]*\b", text))):
        if var not in source:
            missing.append(f"environment variable {var}")
    assert not missing, f"{doc} names what is not there: {sorted(set(missing))}"
