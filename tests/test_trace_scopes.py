"""The train step names its own device ops (apex_tpu/profiler.py's scope
vocabulary), the Pallas kernels carry stable names, and ``TrainLoop`` /
the loaders annotate their host phases. CPU, no capture: the scopes are
read from the lowered and compiled programs' ``op_name`` metadata, the
annotations through a recorder in ``jax.profiler.TraceAnnotation``'s
place."""

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp, profiler
from apex_tpu.data import CausalLMBatchLoader
from apex_tpu.models import BertConfig, BertForPreTraining, pretraining_loss
from apex_tpu.models.gpt import GPTConfig, GPTLMHeadModel, lm_loss
from apex_tpu.optimizers import FusedAdam, FusedLAMB
from apex_tpu.parallel import DistributedDataParallel
from apex_tpu.train import TrainLoop, build_train_step
from benchmark.harness import scopes
from benchmark.harness.manifest import Manifest

ROOT = Path(__file__).resolve().parents[1]
ACCUM = 2        # so the scan is a real loop and the reduce has work
SEQ = 32

COMMON = profiler.STEP_SCOPES
BERT_SCOPES = COMMON + (profiler.LAMB_GRAD_NORM, profiler.LAMB_STAGE1,
                        profiler.LAMB_STAGE2, profiler.MLM_HEAD,
                        profiler.NSP_HEAD, profiler.PRETRAINING_LOSS)
GPT_SCOPES = COMMON + (profiler.ADAM_UPDATE, profiler.LM_HEAD,
                       profiler.LM_LOSS)


def _under(scope, path):
    """``scope`` is a component of the op_name ``path``, bare or wrapped
    by a transform (``jvp(scope)``, ``transpose(jvp(scope))``)."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)", path)


def _bert(rows):
    cfg = BertConfig.tiny(dtype=jnp.bfloat16, fused_kernels=True)
    model = BertForPreTraining(cfg)
    ids = jnp.zeros((rows, SEQ), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids, ids, ids)["params"]

    def loss_fn(p, mb):
        mlm, nsp = model.apply(
            {"params": p}, mb["ids"], mb["ids"] * 0, mb["ids"] * 0 + 1,
            deterministic=False,
            rngs={"dropout": jax.random.PRNGKey(mb["seed"][0])},
            masked_positions=mb["pos"])
        return pretraining_loss(mlm, nsp, mb["lab"], mb["nsp"], None)

    rng = np.random.RandomState(0)
    batch = {"ids": rng.randint(0, cfg.vocab_size, (ACCUM, rows, SEQ)),
             "pos": rng.randint(0, SEQ, (ACCUM, rows, 4)),
             "lab": rng.randint(0, cfg.vocab_size, (ACCUM, rows, 4)),
             "nsp": rng.randint(0, 2, (ACCUM, rows))}
    batch = {k: jnp.asarray(v, jnp.int32) for k, v in batch.items()}
    return params, loss_fn, FusedLAMB(lr=1e-3), batch


def _gpt(rows):
    cfg = GPTConfig.tiny(dtype=jnp.bfloat16, fused_kernels=True, dropout=0.1)
    model = GPTLMHeadModel(cfg)
    ids = jnp.zeros((rows, SEQ), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    def loss_fn(p, mb):
        logits = model.apply(
            {"params": p}, mb["ids"], deterministic=False,
            rngs={"dropout": jax.random.PRNGKey(mb["seed"][0])})
        return lm_loss(logits, mb["ids"])

    rng = np.random.RandomState(1)
    batch = {"ids": jnp.asarray(
        rng.randint(0, cfg.vocab_size, (ACCUM, rows, SEQ)), jnp.int32)}
    return params, loss_fn, FusedAdam(lr=1e-3), batch


def _build(make, rows=2, mesh=None, ddp=None):
    params, loss_fn, opt, batch = make(rows)
    shards = 1 if mesh is None else mesh.devices.size
    batch["seed"] = jnp.arange(ACCUM * shards, dtype=jnp.int32).reshape(
        ACCUM, shards)
    params, opt, handle = amp.initialize(params, opt, opt_level="O2",
                                         verbosity=0)
    step = build_train_step(loss_fn, opt, amp=handle, accum_steps=ACCUM,
                            with_grad_norm=True, ddp=ddp, mesh=mesh)
    return step, step.init(params), batch


def _paths(lowered):
    """The op_name paths of a lowered program's locations (those of an
    inner function, a scan body or a remat, start at that function)."""
    return set(re.findall(r'loc\("([^"]*)"',
                          lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def programs():
    """{model: (lowered paths, compiled HLO text)} of the two tiny steps."""
    out = {}
    for name, make in (("bert_lamb", _bert), ("gpt_adam", _gpt)):
        step, state, batch = _build(make)
        lowered = step.lower(state, batch)
        out[name] = (_paths(lowered), lowered.compile().as_text())
    return out


@pytest.fixture(scope="module")
def table():
    return scopes.load_table(Manifest(ROOT))


CASES = ([("bert_lamb", s) for s in BERT_SCOPES]
         + [("gpt_adam", s) for s in GPT_SCOPES])


@pytest.mark.parametrize("model,scope", CASES,
                         ids=[f"{m}-{s}" for m, s in CASES])
def test_scope_is_in_the_lowered_step(programs, model, scope):
    paths, _ = programs[model]
    assert any(_under(scope, p) for p in paths), scope


@pytest.mark.parametrize("model", ["bert_lamb", "gpt_adam"])
def test_backward_and_recomputation_sit_under_train_fwd_bwd(programs, model):
    paths = set(scopes.parse_hlo(programs[model][1]).op_name.values())
    marked = [p for p in paths
              if "transpose(" in p or "rematted_computation" in p]
    assert len(marked) > 50
    assert all(_under(profiler.TRAIN_FWD_BWD, p) for p in marked)
    # JAX's own markers tell the three passes apart
    assert any("rematted_computation" in p for p in marked)
    assert any("transpose(" in p and "rematted_computation" not in p
               for p in marked)
    # the optimizer's stages nest under the step's optimizer scope
    stage = (profiler.LAMB_STAGE1 if model == "bert_lamb"
             else profiler.ADAM_UPDATE)
    staged = [p for p in paths if _under(stage, p)]
    assert staged and all(_under(profiler.OPTIMIZER_UPDATE, p)
                          for p in staged)


@pytest.mark.parametrize("model", ["bert_lamb", "gpt_adam"])
def test_compiled_instructions_carry_a_vocabulary_scope(programs, table,
                                                        model):
    """At least 98% of the compiled module's fusion / dot / convolution /
    reduce / custom-call instructions are filed under a scope of the
    vocabulary (an instruction without an ``op_name`` of its own is filed
    as the benchmark's reader files it)."""
    _, text = programs[model]
    program = scopes.parse_hlo(text)
    kind = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (?:\([^=]*?\)|\S+) "
                      r"(fusion|dot|convolution|reduce|custom-call)\(")
    names = [m.group(1) for m in map(kind.match, text.splitlines()) if m]
    assert len(names) > 500
    scoped = sum(table.scoped(scopes.path_of(program, n)) for n in names)
    assert scoped >= 0.98 * len(names), (scoped, len(names))
    own = sum(table.scoped(program.op_name[n]) for n in names)
    assert own >= 0.75 * len(names), (own, len(names))


def test_kernel_names_are_in_the_program(programs):
    paths = programs["gpt_adam"][0] | programs["bert_lamb"][0]
    for kernel in ("flash_fwd", "flash_bwd", "layer_norm_bwd",
                   "softmax_fwd", "softmax_bwd", "dropout_apply"):
        assert any(re.search(r"(^|/)" + kernel + r"(/|$)", p)
                   for p in paths), kernel


@pytest.fixture(scope="module", params=[True, False],
                ids=["flat", "buckets"])
def ddp_paths(request):
    mesh = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
    ddp = DistributedDataParallel("data", delay_allreduce=request.param,
                                  message_size=20_000)
    step, state, batch = _build(_gpt, rows=4, mesh=mesh, ddp=ddp)
    return _paths(step.lower(state, batch))


@pytest.mark.parametrize("scope", profiler.DDP_SCOPES)
def test_ddp_scopes_on_four_devices(ddp_paths, scope):
    under = [p for p in ddp_paths if _under(scope, p)]
    assert under and all(_under(profiler.TRAIN_REDUCE, p) for p in under)
    if scope == profiler.DDP_ALLREDUCE:
        assert any(p.endswith("psum") for p in under)


# -- the vocabulary itself --------------------------------------------------------

def test_scope_names_can_become_instruction_names():
    names = profiler.SCOPES + profiler.KERNEL_NAMES + profiler.ANNOTATIONS
    assert len(set(names)) == len(names)
    for name in names:
        assert re.fullmatch(r"[a-z][a-z0-9_]*", name), name
    for name in profiler.SCOPES:       # the docstring table lists each
        assert re.search(r"^" + name + r"\s", profiler.__doc__, re.M), name


def test_benchmark_table_knows_the_vocabulary(table):
    for name in profiler.SCOPES:
        for path in (f"jit(step)/{name}/add", f"jit(s)/jvp({name})/mul",
                     f"jit(s)/a/transpose(jvp({name}))/b"):
            assert table.scoped(path), path
    for path in ("", "jit(step)/while/body/add", "jit(s)/layer_0/q/dot",
                 "jit(s)/my_train_fwd_bwd_2/add"):
        assert not table.scoped(path), path
    phase = {name: table.phase(f"jit(s)/train_fwd_bwd/jvp(M)/{name}/mul")
             for name in profiler.SCOPES}
    assert phase[profiler.OPTIMIZER_UPDATE] == "optimizer"
    assert {phase[s] for s in profiler.DDP_SCOPES} == {"allreduce"}
    assert {phase[s] for s in profiler.MODEL_SCOPES} == {"head"}
    assert {phase[s] for s in profiler.SCOPES if s.startswith("amp_")} == {
        "amp"}


@pytest.mark.parametrize("module", ["flash_attention", "layer_norm",
                                    "softmax", "dropout", "short_conv"])
def test_every_train_path_kernel_has_a_stable_name(module):
    text = (ROOT / "apex_tpu" / "ops" / f"{module}.py").read_text()
    calls = len(re.findall(r"pl\.pallas_call\(", text))
    names = re.findall(r'^\s+name="(\w+)",$', text, re.M)
    # a flash call that may carry a mask description is named by kind and,
    # under a description, by the description's tag as well, and by ``mla``
    # where v's head size is not q's
    kinds = re.findall(
        r'^\s+name=_kernel_name\("(\w+)", score_mask, q, v\),$', text,
        re.M)
    assert calls and len(names) + len(kinds) == calls
    assert bool(kinds) == (module == "flash_attention")
    names += [f"flash_{k}" for k in kinds]
    names += [f"flash_{tag}_{k}" for k in kinds
              for tag in ("blockdiff", "window", "mla")]
    assert set(names) <= set(profiler.KERNEL_NAMES)


# -- host annotations ---------------------------------------------------------------

class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records enters."""

    log = []

    def __init__(self, name, **_):
        self.name = name

    def __enter__(self):
        _Recorder.log.append(self.name)
        return self

    def __exit__(self, *exc):
        _Recorder.log.append("/" + self.name)
        return False


def _toy_step():
    def loss_fn(p, mb):
        return jnp.mean((mb["x"] @ p["w"] - mb["y"]) ** 2)

    step = build_train_step(loss_fn, FusedAdam(lr=1e-2), donate=False)
    state = step.init({"w": jnp.ones((4, 2), jnp.float32)})
    rng = np.random.RandomState(0)
    batches = [{"x": jnp.asarray(rng.randn(1, 3, 4), jnp.float32),
                "y": jnp.asarray(rng.randn(1, 3, 2), jnp.float32)}
               for _ in range(3)]
    return step, state, batches


def test_train_loop_annotates_dispatch_then_fetch(monkeypatch):
    step, state, batches = _toy_step()
    plain = TrainLoop(step, state).run(batches)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    _Recorder.log = []
    loop = TrainLoop(step, state)
    seen = [loop.step(b) for b in batches]
    d, f = profiler.TRAIN_DISPATCH, profiler.TRAIN_FETCH
    # the first step has nothing to fetch; then dispatch -> fetch, once each
    assert _Recorder.log == [d, "/" + d] + [d, "/" + d, f, "/" + f] * 2
    _Recorder.log = []
    last = loop.drain()
    assert _Recorder.log == [f, "/" + f]
    assert loop.drain() is None and _Recorder.log == [f, "/" + f]
    # the outputs are what the loop gives without a recorder
    assert seen[0] is None
    assert seen[1:] + [last] == plain


def test_loader_annotates_its_blocking_wait(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    _Recorder.log = []
    corpus = np.arange(8 * 16, dtype=np.int32).reshape(8, 16)
    it = iter(CausalLMBatchLoader(corpus, batch_size=2, seed=3, prefetch=2))
    w = profiler.DATA_WAIT
    next(it)
    assert _Recorder.log == [w, "/" + w]
    assert len(list(it)) == 3                 # the epoch's other batches
    assert _Recorder.log == [w, "/" + w] * 5  # + the wait that ends it
