"""chip_smoke.py rehearsed on the CPU, and the pieces that carry it.

The phases of chip_smoke.py are functions of a configuration: here each
runs at a tiny configuration on the CPU backend (the four-chip phase on
four of the eight virtual devices), so wrong paths, arguments and control
flow are found before any chip time is spent. The command line offers no
such size: `python chip_smoke.py` on a CPU backend must exit non-zero.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)        # chip_smoke.py lives at the root

import chip_smoke  # noqa: E402
from paddle_tpu.profiler import metrics  # noqa: E402


def debug_config(**over):
    """The smallest configuration every phase runs at: float32, widths
    whose heads tile (head_dim 64) so interpret-mode kernels apply."""
    from paddle_tpu.models import llama

    train = dataclasses.replace(
        llama.LLAMA_PRESETS["debug"], hidden_size=256,
        num_attention_heads=4, num_key_value_heads=4)
    serving = dict(vocab_size=train.vocab_size, hidden_size=128,
                   num_layers=2, num_heads=2, num_kv_heads=2, ffn_size=256,
                   block_size=8, num_blocks=5 * 6 + 1, max_batch=8,
                   max_blocks_per_seq=6, token_budget=32, dtype="float32")
    hybrid = dict(
        vocab_size=128, hidden_size=64,
        hybrid_override_pattern="MEMEMEMEM*E", layer_norm_epsilon=1e-5,
        mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        conv_kernel=4, chunk_size=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, n_routed_experts=16,
        num_experts_per_tok=3, moe_latent_size=32, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=96, routed_scaling_factor=2.5,
        norm_topk_prob=True, held=(4, 4), dtype="float32")
    hybrid_serving = dict(serving, vocab_size=128, hidden_size=64,
                          num_layers=11, num_heads=4, num_kv_heads=2)
    base = dict(llama=train, seq=128, batch=4, steps=3, serving=serving,
                prompt_lens=(30, 5, 12, 21, 8), n_late=2, max_new=8,
                kernels=False, hybrid=hybrid, hybrid_serving=hybrid_serving)
    base.update(over)
    return chip_smoke.SmokeConfig(**base)


# ---------------------------------------------------------------------------
# the phases, at the debug configuration
# ---------------------------------------------------------------------------

def test_phase_train_on_cpu():
    out = chip_smoke.phase_train(debug_config(), jax.devices()[:1])
    assert out["losses"][-1] < out["losses"][0]
    assert out["kernel_calls"]["total"] == 0     # no kernels on the CPU
    assert out["reference_dispatches"] == 0
    assert out["predicted_bytes"] > 0
    json.dumps(out)


def test_phase_train_demands_kernels_when_configured():
    """kernels=True on a backend that puts none in the program is a failed
    phase, not a passing one."""
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.phase_train(debug_config(kernels=True),
                               jax.devices()[:1])


def test_phase_kernels_interpret_mode(monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    out = chip_smoke.phase_kernels(debug_config(), jax.devices()[:1])
    assert out["reference_dispatches"] == 0      # the shapes tile
    assert out["flash_fwd_max_abs_err"] < out["tolerance"]
    assert out["flash_bwd_max_rel_err"] < out["tolerance"]
    json.dumps(out)


def test_phase_serve_on_cpu():
    out = chip_smoke.phase_serve(debug_config(), jax.devices()[:1])
    assert out["finished"] == out["requests"] == 5
    assert out["decoding_at_late_admit"] > 0     # joined mid-flight
    assert out["streams_equal"] == 5             # float32: token for token
    assert out["window_tokens"] > 0
    json.dumps(out)


def test_phase_serve_hybrid_on_cpu():
    out = chip_smoke.phase_serve_hybrid(debug_config(), jax.devices()[:1])
    assert out["finished"] == out["requests"] == 5
    assert out["decoding_at_late_admit"] > 0
    assert out["streams_equal"] == 5             # float32: token for token
    assert out["kernel_calls"]["ssm_state_update"] == 0   # kernels off
    assert out["kernel_calls"]["kv_page_write"] == 0
    json.dumps(out)


def test_phase_serve_hybrid_demands_its_kernels_when_configured():
    """Kernels expected and none in the compiled step (the CPU runs the
    references): a failed phase, not a quiet pass."""
    with pytest.raises(AssertionError,
                       match="kernel calls in the hybrid mixed step"):
        chip_smoke.phase_serve_hybrid(debug_config(kernels=True),
                                      jax.devices()[:1])


def test_phase_serve_demands_the_paged_kernel_when_configured(monkeypatch):
    """The serve phase as the chip runs it, kernels expected: widths whose
    heads and pages tile (head_dim 128, 32-token pages), kernels in
    interpret mode. Nothing on the engine's path gives way to a reference
    (that check comes first and passes), but an interpreted kernel is no
    `tpu_custom_call` in the lowered step: a failed phase, not a quiet
    pass, as for the train step."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    serving = dict(debug_config().serving, hidden_size=256, block_size=32,
                   num_blocks=5 * 2 + 1, max_blocks_per_seq=2,
                   token_budget=128)
    with pytest.raises(AssertionError,
                       match="kernel calls in the engine's mixed step.*"
                             "'paged_attention': 2, 'kv_page_write': 2"):
        chip_smoke.phase_serve(
            debug_config(serving=serving, kernels=True), jax.devices()[:1])


def test_phase_eager_on_cpu():
    out = chip_smoke.phase_eager(debug_config(), jax.devices()[:1])
    assert out["device"] == "cpu"
    assert len(out["ops"]) >= 12 and "fft" in out["ops"]
    assert out["fft_dtype"] == "complex64"
    json.dumps(out)


@pytest.mark.parametrize("interpret", ["0", "1"])
def test_phase_multichip_on_four_virtual_devices(monkeypatch, interpret):
    """The four-chip rehearsal. With interpret-mode kernels on, the
    trunk's kernels run per shard inside shard_map (ops.pallas.per_shard)
    — the path the chip takes — and must give the same trajectory."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", interpret)
    out = chip_smoke.phase_multichip(debug_config(), jax.devices()[:4])
    assert [r["layout"] for r in out["runs"]] == [
        {"sharding": 2, "mp": 2}, {"mp": 4}, {"pp": 2, "mp": 2}]
    assert [r["pipelined"] for r in out["runs"]] == [False, False, True]
    for run in out["runs"]:
        assert run["worst_rel_diff"] < out["tolerance"]
        assert len(run["param_bytes_share_per_device"]) == 4
    assert out["runs"][1]["param_bytes_share_per_device"][0] \
        == pytest.approx(0.25, abs=0.01)
    json.dumps(out)


def test_check_placement_catches_everything_on_one_device():
    """The failure the four-chip phase exists to catch: parameters that
    all sit on the first device."""
    from paddle_tpu.distributed.fleet.trainer import HybridTrainer
    from paddle_tpu.distributed.topology import build_mesh

    cfg = debug_config()
    devices = jax.devices()[:4]
    tr = HybridTrainer(cfg.llama, build_mesh(sharding=2, mp=2,
                                             devices=devices))
    tr.params = jax.tree.map(
        lambda a: jax.device_put(np.asarray(a), devices[0]), tr.params)
    with pytest.raises(AssertionError, match="1 of 4 devices"):
        chip_smoke.check_placement(tr, devices)


def test_kernel_calls_in_counts_by_family():
    text = "\n".join([
        'x = stablehlo.custom_call @tpu_custom_call(%0) {kernel_name = '
        '"flash_attention_fwd"}',
        'y = stablehlo.custom_call @tpu_custom_call(%1) {kernel_name = '
        '"flash_attention_dq"}',
        'z = stablehlo.custom_call @tpu_custom_call(%2) {kernel_name = '
        '"rms_norm_noweight"}',
        'w = stablehlo.custom_call @tpu_custom_call(%3) {kernel_name = '
        '"varlen_attention_fwd"}',
        'p = stablehlo.custom_call @tpu_custom_call(%4) {kernel_name = '
        '"paged_attention"}'])
    assert chip_smoke.kernel_calls_in(text) == {
        "flash_attention": 2, "varlen_attention": 1, "rms_norm": 1,
        "paged_attention": 1, "ssm_state_update": 0, "kv_page_write": 0,
        "total": 5}
    # a compiled program's text: the custom calls' instruction names
    compiled = "HloModule jit_serving_step\n" + "\n".join(
        f'  %{name} = bf16[8,1024,128]{{2,1,0}} custom-call(%p), '
        f'custom_call_target="tpu_custom_call", kernel_metadata={{}}'
        for name in ("paged_attention.2", "paged_attention.3",
                     "rms_norm.7", "closed_call_varlen_attention_fwd.1"))
    assert chip_smoke.kernel_calls_in(compiled) == {
        "flash_attention": 0, "varlen_attention": 1, "rms_norm": 1,
        "paged_attention": 2, "ssm_state_update": 0, "kv_page_write": 0,
        "total": 4}


@pytest.mark.parametrize("compiled", [False, True])
def test_kernel_calls_in_knows_the_page_write_kernel(compiled):
    """`kv_page_write` beside `paged_attention`, one call a layer, in a
    lowered program's `kernel_name`s and in a compiled program's
    instruction names (which JAX's wrappers may prefix)."""
    if compiled:
        text = "HloModule jit_serving_step\n" + "\n".join(
            f'  %{name} = (bf16[2,9,2,32,128]{{4,3,2,1,0}}, '
            f'bf16[2,9,2,32,128]{{4,3,2,1,0}}) custom-call(%p), '
            f'custom_call_target="tpu_custom_call", kernel_metadata={{}}'
            for name in ("kv_page_write.1", "closed_call_kv_page_write.2",
                         "paged_attention.2", "paged_attention.3"))
    else:
        text = "\n".join(
            f'x = stablehlo.custom_call @tpu_custom_call(%0) '
            f'{{kernel_name = "{name}"}}'
            for name in ("kv_page_write", "kv_page_write",
                         "paged_attention", "paged_attention"))
    calls = chip_smoke.kernel_calls_in(text)
    assert calls["kv_page_write"] == calls["paged_attention"] == 2
    assert calls["total"] == 4


def test_whole_array_copies_in_counts_copies_of_one_shape():
    """A `copy` whose result is the stack (in any layout) counts, a copy of
    another shape and a fusion of the stack's shape do not; a program that
    never holds the stack is an error, not a zero."""
    stack = jax.ShapeDtypeStruct((2, 9, 2, 32, 128), jnp.bfloat16)
    text = "HloModule jit_serving_step\n" + "\n".join([
        "  %copy.1 = bf16[2,9,2,32,128]{4,2,3,1,0:T(8,128)(2,1)} copy(%p.7)",
        "  %copy.2 = bf16[2,9,2,32,128]{4,3,2,1,0} copy(%fusion.3)",
        "  %copy.3 = bf16[256,2,128]{2,0,1} copy(%p.4)",
        "  %fusion.3 = bf16[2,9,2,32,128]{4,2,3,1,0} fusion(%copy.1)"])
    assert chip_smoke.whole_array_copies_in(text, stack) == 2
    with pytest.raises(AssertionError, match=r"no f32\[5,3\]"):
        chip_smoke.whole_array_copies_in(
            text, jax.ShapeDtypeStruct((5, 3), jnp.float32))


# ---------------------------------------------------------------------------
# the command line
# ---------------------------------------------------------------------------

def _run(args, **env_over):
    env = dict(os.environ)
    env.update(env_over)
    return subprocess.run([sys.executable,
                           os.path.join(REPO, "chip_smoke.py")] + args,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_help_and_argument_handling_start_no_backend():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke\n"
        "a = chip_smoke.parse_args(['--chips', '4', '--seed', '7'])\n"
        "assert (a.chips, a.seed) == (4, 7)\n"
        "try:\n"
        "    chip_smoke.parse_args(['--help'])\n"
        "except SystemExit as e:\n"
        "    assert e.code == 0\n"
        "assert 'jax' not in sys.modules, 'argument handling imported jax'\n"
        % REPO)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "--chips" in r.stdout


def test_command_line_offers_no_other_size_or_platform():
    r = _run(["--help"])
    assert r.returncode == 0
    options = {w for w in r.stdout.split() if w.startswith("--")}
    assert options <= {"--help", "--chips", "--seed"}, options
    assert _run(["--chips", "2"]).returncode != 0


def test_cpu_backend_exits_nonzero_and_names_the_missing_chip(tmp_path):
    r = _run([], JAX_PLATFORMS="cpu",
             JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode != 0
    assert "no TPU chip" in r.stderr
    assert '"ok"' not in r.stdout            # no result line


# ---------------------------------------------------------------------------
# the compile-cache helper
# ---------------------------------------------------------------------------

_CACHE_PROBE = (
    "import sys; sys.path.insert(0, %r)\n"
    "from paddle_tpu.utils.compile_cache import enable_compile_cache\n"
    "import jax\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n" % REPO)


def _cache_probe(cwd, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_over)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=cwd,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_compile_cache_fixed_path_from_any_working_directory(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    a = _cache_probe(str(tmp_path))
    b = _cache_probe(REPO)
    assert a == b == [want, want]


def test_compile_cache_honours_the_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no directory in
    code: what JAX holds is what JAX read from the environment."""
    got = _cache_probe(REPO, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert got == [str(tmp_path), str(tmp_path)]


def test_package_import_sets_no_cache_directory():
    code = ("import sys; sys.path.insert(0, %r)\n"
            "import paddle_tpu, jax\n"
            "print(jax.config.jax_compilation_cache_dir)\n" % REPO)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "None"


# ---------------------------------------------------------------------------
# the reference-dispatch counter (interpret mode)
# ---------------------------------------------------------------------------

def _count():
    return metrics.counter("pallas/reference_dispatch").value


@pytest.mark.parametrize("kernel,tiles,expect_moves", [
    ("flash_attention", True, False),
    ("flash_attention", False, True),
    ("rms_norm", True, False),
    ("rms_norm", False, True),
    ("varlen_attention", True, False),
    ("varlen_attention", False, True),
])
def test_reference_dispatch_counter(monkeypatch, kernel, tiles,
                                    expect_moves):
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import rms_norm as rn
    from paddle_tpu.ops.pallas import varlen_attention as va

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    before = _count()
    site = {"flash_attention": "flash_attention_fwd"}.get(kernel, kernel)
    named = metrics.counter("pallas/reference_dispatch/" + site)
    named_before = named.value
    by_kernel_before = chip_smoke.reference_dispatches()
    if kernel == "rms_norm":
        rows = 512 if tiles else 300        # 300 % 256 != 0
        rn.rms_norm(jnp.ones((rows, 128)), jnp.ones((128,)), 1e-5)
    else:
        s = 128 if tiles else 96            # 96 is not lane aligned
        q = jnp.ones((1, 2, s, 64), jnp.float32)
        if kernel == "flash_attention":
            fa.flash_attention_bhsd(q, q, q, is_causal=True)
        else:
            seg = jnp.zeros((1, s), jnp.int32)
            va.varlen_flash_attention_packed(q, q, q, seg, seg,
                                             is_causal=True)
    moved = _count() - before
    assert (moved > 0) == expect_moves, moved
    assert (named.value - named_before > 0) == expect_moves
    # what chip_smoke's assertion prints: the kernels that gave way
    assert list(chip_smoke.dispatches_since(by_kernel_before)) \
        == ([site] if expect_moves else [])


def test_reference_dispatch_not_counted_when_kernels_are_off(monkeypatch):
    """With kernels off the reference IS the configured path, not a
    fallback: nothing to count."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import rms_norm as rn

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "0")
    monkeypatch.setenv("PT_USE_PALLAS", "0")
    before = _count()
    rn.rms_norm(jnp.ones((300, 128)), jnp.ones((128,)), 1e-5)
    assert _count() == before


def test_use_pallas_does_not_swallow_a_failing_backend(monkeypatch):
    from paddle_tpu.ops import pallas

    monkeypatch.setenv("PT_USE_PALLAS", "auto")

    def boom():
        raise RuntimeError("backend failed to start")

    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="failed to start"):
        pallas.use_pallas()


# ---------------------------------------------------------------------------
# one process for each chip
# ---------------------------------------------------------------------------

def test_launcher_import_initialises_no_backend():
    """The launcher parent must stay off JAX's backends: a parent that
    holds the chip starves its workers."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import paddle_tpu\n"
        "import paddle_tpu.distributed.launch.main\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n" % REPO)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("nproc,platforms,chips,refused", [
    (2, "", 4, True),           # chip host, workers would take the TPU
    (2, "tpu", 4, True),
    (2, "cpu", 4, False),       # CPU workers: what the test suite runs
    (1, "", 4, False),          # one controller drives all local chips
    (2, "", 0, False),          # no chips on this host
])
def test_launcher_one_process_per_chip(monkeypatch, nproc, platforms,
                                       chips, refused):
    from paddle_tpu.distributed.launch import main as launch_main

    monkeypatch.setattr(launch_main, "_local_tpu_chips", lambda: chips)
    env = {"JAX_PLATFORMS": platforms} if platforms else {}
    if refused:
        with pytest.raises(SystemExit, match="one process at a time"):
            launch_main.check_one_process_per_chip(nproc, env)
    else:
        launch_main.check_one_process_per_chip(nproc, env)


def test_subprocess_factory_child_platform_is_explicit(monkeypatch,
                                                       tmp_path):
    """The child's platform is the factory's argument, whatever the
    caller's environment says (default: CPU workers)."""
    from paddle_tpu.inference.remote_replica import \
        SubprocessReplicaFactory

    class _Store:
        port = 1

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    for kwargs, want in (({}, "cpu"), ({"child_platform": "tpu"}, "tpu")):
        f = SubprocessReplicaFactory({}, pid_dir=str(tmp_path), **kwargs)
        f._store = _Store()
        assert f._child_env(1, {})["JAX_PLATFORMS"] == want
