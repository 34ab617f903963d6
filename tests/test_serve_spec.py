"""ServeSpec resolution: the automatic loop's public API contract.

- resolution fills EVERY "auto" field with a concrete, deterministic value
  (arch sweep: MoE + dense + a legacy-fallback family);
- resolved specs round-trip through ``dataclasses.replace``;
- explicit overrides beat "auto" field by field;
- the resolved spec — not Engine flag defaults — is what reaches the
  engine (the acceptance criterion of the redesign);
- the old per-knob ``Engine(...)`` / ``Scheduler(token_budget=)`` kwargs
  and ``spec_from_engine_kwargs`` are fully retired (their one-release
  deprecation window closed) — construction without a spec is a TypeError.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.core.topology import CLUSTERS
from repro.kernels.policy import KernelPolicy
from repro.models.model import init_params
from repro.serving.api import AUTO, LLM, ResolvedServeSpec, ServeSpec
from repro.serving.engine import Engine
from repro.serving.scheduler import Scheduler

# one MoE, one dense, one family the unified engine serves via the
# internal legacy fallback (ssm) — resolution must work for all of them
ARCH_SWEEP = ("phi3.5-moe-42b", "smollm-360m", "rwkv6-1.6b")


@pytest.fixture(scope="module")
def smollm():
    cfg = C.get_reduced("smollm-360m")
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return cfg, params


@pytest.mark.parametrize("arch", ARCH_SWEEP)
def test_resolution_concrete_and_deterministic(arch):
    spec = ServeSpec(arch=arch, prompt_len=32, max_new_tokens=8)
    r1 = spec.resolve()
    r2 = spec.resolve()
    for f in ("chunk", "token_budget", "max_batch", "max_len"):
        v = getattr(r1, f)
        assert isinstance(v, int) and v > 0, (f, v)
    assert r1.dispatch in ("dropless", "capacity")
    assert isinstance(r1.kernels, KernelPolicy)
    assert r1.strategy in ("mixserve", "dp_ep", "pure_ep", "pure_tp")
    assert r1.cluster in CLUSTERS
    assert r1 == r2                                 # deterministic
    # every knob has a provenance entry, and auto fields say so
    assert set(r1.provenance) >= set(ResolvedServeSpec._KNOBS)
    for f in ("strategy", "chunk", "token_budget", "max_batch", "max_len",
              "kernels", "dispatch"):
        assert r1.provenance[f].startswith("auto:"), (f, r1.provenance[f])
    assert arch in r1.describe()                    # printable report


@pytest.mark.parametrize("arch", ARCH_SWEEP)
def test_resolved_spec_roundtrips_replace(arch):
    r = ServeSpec(arch=arch).resolve()
    assert dataclasses.replace(r) == r
    bumped = dataclasses.replace(r, chunk=r.chunk + 1)
    assert bumped.chunk == r.chunk + 1
    assert dataclasses.replace(bumped, chunk=r.chunk) == r


def test_explicit_overrides_beat_auto():
    spec = ServeSpec(arch="phi3.5-moe-42b", strategy="mixserve",
                     kernels="off", dispatch="capacity", chunk=5,
                     token_budget=11, max_batch=3, max_len=80)
    r = spec.resolve()
    assert (r.chunk, r.token_budget, r.max_batch, r.max_len) == (5, 11, 3, 80)
    assert r.dispatch == "capacity" and not r.kernels.any_enabled
    assert r.strategy == "mixserve"
    for f in ("strategy", "kernels", "dispatch", "chunk", "token_budget",
              "max_batch", "max_len"):
        assert r.provenance[f].startswith("explicit"), f
    # the plan carries the explicit kernel/dispatch choice
    assert r.plan.dispatch_mode == "capacity"
    assert r.plan.kernels == KernelPolicy.off()


def test_spec_field_validation():
    with pytest.raises(ValueError):
        ServeSpec(arch="smollm-360m", dispatch="bogus")
    with pytest.raises(ValueError):
        ServeSpec(arch="smollm-360m", strategy="bogus")
    with pytest.raises(ValueError):
        ServeSpec(arch="smollm-360m", chunk="sixteen")
    with pytest.raises(ValueError):
        ServeSpec().resolve()          # no arch, no cfg


def test_explicit_cluster_name_and_mismatch():
    r = ServeSpec(arch="smollm-360m", cluster="h20x16").resolve()
    assert r.cluster == "h20x16" and r.provenance["cluster"] == "explicit"
    with pytest.raises(KeyError):
        ServeSpec(arch="smollm-360m", cluster="nope").resolve()


def test_resolved_spec_reaches_engine(smollm):
    """Acceptance: the analyzer/cost-model-resolved knobs — not the old
    Engine flag defaults (max_batch=8/max_len=512/chunk=16, budget
    B*chunk) — are what configures the engine and scheduler."""
    cfg, params = smollm
    r = ServeSpec(arch="smollm-360m", prompt_len=16,
                  max_new_tokens=4).resolve()
    eng = Engine(cfg, params, spec=r)
    assert eng.spec is r
    assert eng.max_batch == r.max_batch and eng.max_len == r.max_len
    assert eng.chunk == r.chunk
    assert eng.plan is r.plan
    assert eng.plan.dispatch_mode == r.dispatch == "dropless"
    assert eng.plan.kernels == r.kernels
    assert eng.temperature == r.temperature
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # no deprecation on the new API
        sched = Scheduler(eng)
    assert sched.token_budget == r.token_budget
    # the resolved values are live, not the legacy defaults
    assert eng.max_len != 512
    assert sched.token_budget != eng.max_batch * eng.chunk


def test_engine_kwargs_shim_retired(smollm):
    """The PR 5 deprecation window is closed: per-knob Engine kwargs, the
    Scheduler(token_budget=) kwarg and spec_from_engine_kwargs are gone."""
    cfg, params = smollm
    with pytest.raises(TypeError):
        Engine(cfg, params, max_batch=3, max_len=48, chunk=4)
    with pytest.raises(TypeError):
        Engine(cfg, params)                      # a spec is mandatory
    r = ServeSpec(arch="smollm-360m", max_batch=2, max_len=64,
                  prompt_len=8, max_new_tokens=2).resolve()
    eng = Engine(cfg, params, spec=r)
    with pytest.raises(TypeError):
        Scheduler(eng, token_budget=7)
    import repro.serving.api as api
    assert not hasattr(api, "spec_from_engine_kwargs")


def test_overload_resolves_from_cost_model():
    """The "auto" overload knob becomes a concrete bounded-admission policy
    priced by the Eq. 4-6 token-time estimates, with provenance; an explicit
    OverloadPolicy passes through untouched."""
    from repro.core.resolve import OverloadPolicy

    r = ServeSpec(arch="smollm-360m", prompt_len=16, max_new_tokens=4).resolve()
    assert isinstance(r.overload, OverloadPolicy)
    assert r.overload.queue_cap >= 2 * r.max_batch
    assert r.overload.est_request_s > 0
    assert r.overload.shed == "deadline-first"
    assert r.provenance["overload"].startswith("auto:cost-model")
    assert "overload" in r.describe()

    pol = OverloadPolicy(queue_cap=3, shed="reject-newest")
    r2 = ServeSpec(arch="smollm-360m", overload=pol).resolve()
    assert r2.overload is pol
    assert r2.provenance["overload"] == "explicit"
    # validation
    with pytest.raises(ValueError):
        OverloadPolicy(queue_cap=0)
    with pytest.raises(ValueError):
        OverloadPolicy(queue_cap=4, shed="bogus")
    with pytest.raises(ValueError):
        ServeSpec(arch="smollm-360m", overload="bogus")


def test_faults_field_validated_and_in_meta():
    from repro.serving.faults import Fault

    f = Fault(kind="latency", at=(3,), ms=5.0)
    r = ServeSpec(arch="smollm-360m", faults=[f]).resolve()
    assert r.faults == (f,)                     # normalized to a tuple
    assert r.as_meta()["faults"] == [f.describe()]
    with pytest.raises(ValueError):
        ServeSpec(arch="smollm-360m", faults=("nan",))   # not a Fault
    with pytest.raises(ValueError):
        Fault(kind="bogus", at=(1,))
    with pytest.raises(ValueError):
        Fault(kind="nan")                       # never fires


def test_llm_generate_and_stream_agree(smollm):
    cfg, params = smollm
    r = ServeSpec(arch="smollm-360m", prompt_len=16, max_new_tokens=4,
                  max_batch=2, max_len=64, chunk=4).resolve()
    prompts = [np.arange(5, dtype=np.int32) % cfg.vocab_size,
               np.arange(7, dtype=np.int32) % cfg.vocab_size]

    llm = LLM.from_spec(r, cfg=cfg, params=params)
    outs = llm.generate(prompts, max_new_tokens=4)
    assert [len(o) for o in outs] == [4, 4]

    llm2 = LLM.from_spec(r, cfg=cfg, params=params)
    rids = [llm2.submit(p, 4) for p in prompts]
    got = {rid: [] for rid in rids}
    for rid, tok in llm2.stream():
        got[rid].append(tok)
    assert [got[rid] for rid in rids] == outs


def test_llm_generate_after_submit_does_not_crash(smollm):
    """generate() drains the queue: an earlier submit()'s request must not
    crash the bookkeeping (regression: KeyError on the foreign rid)."""
    cfg, params = smollm
    r = ServeSpec(arch="smollm-360m", prompt_len=16, max_new_tokens=4,
                  max_batch=2, max_len=64, chunk=4).resolve()
    llm = LLM.from_spec(r, cfg=cfg, params=params)
    llm.submit(np.arange(4, dtype=np.int32) % cfg.vocab_size, 3)
    outs = llm.generate([np.arange(6, dtype=np.int32) % cfg.vocab_size],
                        max_new_tokens=2)
    assert [len(o) for o in outs] == [2]
    assert llm.engine.n_active == 0 and not llm._queue


def test_llm_stream_terminates_on_legacy_fallback_single_token():
    """Legacy-fallback families (blocking prefill emits the first token in
    admit): a max_new_tokens=1 request must finish and free its slot
    instead of spinning stream() forever (regression)."""
    cfg = C.get_reduced("rwkv6-1.6b")
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    r = ServeSpec(arch="rwkv6-1.6b", prompt_len=8, max_new_tokens=1,
                  max_batch=1, max_len=64).resolve()
    llm = LLM.from_spec(r, cfg=cfg, params=params)
    assert llm.engine.legacy
    outs = llm.generate([np.arange(5, dtype=np.int32) % cfg.vocab_size],
                        max_new_tokens=1)
    assert [len(o) for o in outs] == [1]
    assert llm.engine.n_active == 0


def test_llm_from_plain_spec_builds_reduced_engine():
    llm = LLM.from_spec(ServeSpec(arch="smollm-360m", prompt_len=8,
                                  max_new_tokens=2, max_batch=1,
                                  max_len=32))
    assert llm.cfg.name == C.get_reduced("smollm-360m").name
    assert llm.engine.max_batch == 1 and llm.engine.max_len == 32
    out = llm.generate([np.arange(4, dtype=np.int32)], max_new_tokens=2)
    assert len(out[0]) == 2


def test_serve_cli_builds_auto_spec_and_reduced_toggle():
    """serve.py flags land on the spec; --reduced is finally disableable."""
    from repro.launch import serve as S
    args = S.parse_args(["--arch", "phi3.5-moe-42b"])
    assert args.reduced is True
    spec = S.build_spec(args)
    for f in ("chunk", "token_budget", "max_batch", "max_len",
              "kernels", "dispatch", "strategy"):
        assert getattr(spec, f) == AUTO, f
    args2 = S.parse_args(["--arch", "phi3.5-moe-42b", "--no-reduced",
                          "--chunk", "8", "--dispatch", "capacity"])
    assert args2.reduced is False
    spec2 = S.build_spec(args2)
    assert spec2.reduced is False
    assert spec2.chunk == 8 and spec2.dispatch == "capacity"


def test_cluster_for_mesh_explicit_and_fallback():
    """launch.auto.cluster_for_mesh: explicit ClusterSpec/name wins and is
    validated against the mesh size; the v5e heuristic is the fallback."""
    from repro.core.topology import TPU_V5E_POD
    from repro.launch.auto import cluster_for_mesh

    class FakeDevices:
        def __init__(self, size):
            self.size = size

    class FakeMesh:
        def __init__(self, size):
            self.devices = FakeDevices(size)

    assert cluster_for_mesh(FakeMesh(256)) is TPU_V5E_POD
    assert cluster_for_mesh(FakeMesh(512)).name == "v5e-2pods-512"
    assert cluster_for_mesh(FakeMesh(16), "h20x16").name == "h20x16"
    assert cluster_for_mesh(FakeMesh(16), CLUSTERS["h20x16"]).name == "h20x16"
    with pytest.raises(ValueError):
        cluster_for_mesh(FakeMesh(8), "h20x16")     # 16 devices != 8


def test_resolve_cluster_reads_device_kind_on_tpu(monkeypatch):
    """With no mesh on a TPU backend the attached chip's device_kind picks
    the cluster; a kind with no ClusterSpec is an error, not a default."""
    import jax

    from repro.core.resolve import resolve_cluster

    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev("TPU v5 lite")])
    spec, prov = resolve_cluster()
    assert spec.name == "v5e-1" and spec.n_devices == 1
    assert spec.peak_flops == 197e12 and prov == "auto:device_kind(TPU v5 lite)"
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev("TPU v9 imaginary")])
    with pytest.raises(KeyError, match="device kind"):
        resolve_cluster()


def test_compile_cache_env_wins_else_fixed_checkout_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and nothing is set
    in code; otherwise the cache is <repo>/.jax_cache, which git ignores."""
    import pathlib

    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert pathlib.Path(path) == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
