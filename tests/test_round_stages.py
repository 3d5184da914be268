"""The round's stage scopes (``ROUND_STAGES``) in the compiled chunk
program: every stage is named, each op sits in the stage that emits it,
scopes never nest, and the names change nothing but metadata."""
import re
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import pytest

from repro.config import FLConfig
from repro.core import GluADFL, gluadfl
from repro.core.gluadfl import ROUND_STAGES
from repro.models import LSTMModel
from repro.optim import adam

N, HIDDEN, WINDOWS, HISTORY = 16, 8, 40, 12
INSTR = re.compile(r"^\s*(?:ROOT )?%?(\S+) = \S+ ([a-z-]+)\(.*op_name=\"([^\"]*)\"")
STAGE = re.compile(r"\bround\.(\w+)")


def compiled_text() -> str:
    """The CPU-compiled chunk program of the benchmark's training plan
    (random graph, 30% inactive, Adam, sparse table) at a small size."""
    cfg = FLConfig(topology="random", num_nodes=N, comm_batch=7, inactive_ratio=0.3)
    tr = GluADFL(LSTMModel(hidden=HIDDEN).as_model(), adam(1e-3), cfg,
                 gossip_repr="sparse")
    state = jax.eval_shape(tr.init, jax.random.PRNGKey(0))
    data = (jax.ShapeDtypeStruct((N, WINDOWS, HISTORY), jnp.float32),
            jax.ShapeDtypeStruct((N, WINDOWS), jnp.float32),
            jax.ShapeDtypeStruct((N,), jnp.int32))
    return tr._chunk_jit.lower(state, *data, None, None, batch_size=16,
                               chunk=4).compile().as_text()


def strip(text: str) -> str:
    """The program without metadata or the stack-frame tables it points at."""
    out, table = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            table = True
        elif table and (not line or line[0].isdigit()):
            continue
        else:
            table = False
            out.append(re.sub(r",? ?metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


@pytest.fixture(scope="module")
def text():
    return compiled_text()


@pytest.fixture(scope="module")
def instrs(text):
    """``(name, opcode, op_name)`` of every instruction with metadata."""
    return [m.groups() for m in map(INSTR.match, text.splitlines()) if m]


def stages_of(op_name: str) -> list:
    return [s for s in STAGE.findall(op_name) if s in ROUND_STAGES]


def test_every_stage_is_named(instrs):
    named = {s for _, _, op in instrs for s in stages_of(op)}
    assert named == set(ROUND_STAGES)


def test_scopes_never_nest(instrs):
    for _, _, op in instrs:
        for stack in op.split(";"):
            assert len(set(stages_of(stack))) <= 1, stack


def test_lstm_forward_and_transposed_dots_are_the_local_step(instrs):
    """The LSTM's matmuls, forward (``jvp``) and backward
    (``transpose(jvp())``), all sit under ``round.local_step``."""
    dots = [op for _, code, op in instrs if code in ("dot", "convolution")]
    lstm = [op for op in dots if "jvp(" in op]
    assert any("transpose(jvp" in op for op in lstm)
    assert any("jvp(" in op and "transpose(" not in op for op in lstm)
    assert all(stages_of(op) == ["local_step"] for op in lstm)


def test_adam_update_is_the_adam_stage(instrs):
    """Adam's square root, the only one in a round, sits under
    ``round.adam``; so does the rest of its update."""
    roots = [op for _, code, op in instrs if code in ("sqrt", "rsqrt")]
    assert roots and all(stages_of(op) == ["adam"] for op in roots)


def test_scopes_change_only_metadata(text, monkeypatch):
    """With every scope taken out, the compiled program is the same op
    for op once metadata is stripped."""
    monkeypatch.setattr(gluadfl, "_stage", lambda name: nullcontext())
    bare = compiled_text()
    assert STAGE.search(bare) is None
    assert strip(bare) == strip(text)
