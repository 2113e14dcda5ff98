"""The training path's named scopes (``models.transformer.SCOPES``): the
compiled train step carries each layer's scope in its HLO ``op_name``
metadata, the scopes change nothing else the compiler emits, and the
benchmark reads the same names."""

import contextlib
import re
import sys
from pathlib import Path

import jax
import pytest

from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh
from repro.launch.train import build_runtime
from repro.models.transformer import SCOPES
from repro.train.optimizer import OptConfig
from repro.train.step import compile_train_step

REPO = Path(__file__).resolve().parents[1]
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _compiled_text(arch: str, plan_kw: dict) -> str:
    _, model, plan = build_runtime(arch, True, plan_kw, remat=False)
    mesh = make_mesh(1, 1, devices=jax.devices()[:1])
    specs = model.input_specs(ShapeConfig("train", 32, 2, "train"))
    lowered, *_ = compile_train_step(model, plan, mesh, OptConfig(), specs)
    return lowered.compile().as_text()


def _scopes_in(text: str) -> set:
    parts = set()
    for op in OP_NAME.findall(text):
        parts.update(re.sub(r"jvp\(|transpose\(|\)", "", op).split("/"))
    return parts & set(SCOPES)


@pytest.mark.parametrize("arch,plan_kw,missing", [
    ("gpt2-1.5b", {"gc": True}, {"moe"}),
    ("moonshot-v1-16b-a3b", {"ga_steps": 2}, set()),
])
def test_train_step_carries_every_scope(arch, plan_kw, missing):
    text = _compiled_text(arch, plan_kw)
    assert _scopes_in(text) == set(SCOPES) - missing
    if plan_kw.get("gc"):
        assert "rematted_computation" in text


def _canonical(text: str) -> list:
    """The module's instructions without metadata, the source-location
    tables it points into, or instruction names (renumbered by order of
    first appearance: the compiler derives some from the name stack)."""
    lines, skip, ids = [], False, {}
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            skip = True
        elif skip and not line:
            skip = False
        elif not skip:
            line = re.sub(r",?\s*metadata=\{[^}]*\}", "", line)
            lines.append(re.sub(r"%[\w.\-]+",
                                lambda m: f"%{ids.setdefault(m.group(0), len(ids))}",
                                line))
    return lines


def test_scopes_change_only_metadata(monkeypatch):
    scoped = _compiled_text("gpt2-1.5b", {"gc": True})
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _compiled_text("gpt2-1.5b", {"gc": True})
    assert _scopes_in(scoped) and not _scopes_in(bare)
    assert _canonical(scoped) == _canonical(bare)


def test_benchmark_reads_the_program_scopes():
    sys.path.insert(0, str(REPO))
    try:
        from perfbench.lib import scopes
    finally:
        sys.path.remove(str(REPO))
    assert scopes.SCOPES == SCOPES
    assert set(scopes.SCOPE_METRICS.values()) <= set(SCOPES)
