"""A CPU rehearsal of ``chip_smoke.py``: the same phase functions at
``LlamaConfig.tiny()`` with the Pallas kernels interpreted (asked for in
``conftest.py``), so a wrong path, argument or control flow is found here and
not on chip time. Nothing in this file is a device measurement.
"""

import argparse
import dataclasses
import importlib
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

from lzy_tpu.models.llama import LlamaConfig  # noqa: E402

# the module: ``lzy_tpu.ops`` exports the function under the same name
paged_attention = importlib.import_module("lzy_tpu.ops.paged_attention")


def _args(**kw):
    return argparse.Namespace(seed=0, chips=1, require_tpu=False, **kw)


@pytest.fixture(scope="module")
def tiny():
    # float32 compute: the rehearsal is about control flow, and float32 keeps
    # every path's greedy tokens identical, so the judge is not needed
    return dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)


def test_serve_phase_rehearsal(tiny, monkeypatch):
    # what "auto" is on the chip, under the interpreter here
    monkeypatch.setattr(paged_attention, "default_kernel", lambda: "pallas")
    out = chip_smoke.phase_serve(_args(), tiny, slots=2, pool={},
                                 new_tokens=8)
    assert out["kernel_path"] == "pallas"
    assert out["cached_prompt_tokens_B"] >= 64
    assert set(out["verdicts"].values()) == {"identical"}
    assert len(out["verdicts"]) == 5
    assert out["tokens_per_second"] > 0 and len(out["ttft_ms"]) == 5
    assert out["changed_from_llama3_8b"]["n_layers"] == ["32", "2"]
    json.dumps(out)                         # the phase line must serialise


def test_train_phase_rehearsal():
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), use_flash_kernel=True, fused_ce=True)
    out = chip_smoke.phase_train(_args(), cfg, batch=2, seq=128, lr=1e-2,
                                 expect_custom_call=False)
    assert len(out["losses"]) == 5 and out["losses"][-1] < out["losses"][0]
    assert out["flash_lowered_as"].startswith("interpreted")
    json.dumps(out)


def test_four_chip_phases_rehearse_on_virtual_devices(tiny):
    """``--chips 4`` on four of the eight virtual CPU devices: the gang
    against one device, and fsdp=4 against one device."""
    wide = dataclasses.replace(tiny, n_heads=8, n_kv_heads=4)
    out = chip_smoke.phase_gang(_args(), wide, slots=2, pool={},
                                new_tokens=8)
    assert set(out["verdicts"].values()) == {"identical"}
    assert len(out["gang"]["pool_bytes_per_device"]) == 4
    assert len(out["one_chip"]["pool_bytes_per_device"]) == 1
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), use_flash_kernel=True, fused_ce=True)
    out = chip_smoke.phase_fsdp(_args(), cfg, batch=4, seq=128, lr=1e-2,
                                expect_custom_call=False)
    assert len(out["fsdp"]["state_bytes_per_device"]) == 4
    assert out["loss_rel_diff"] <= chip_smoke.FSDP_LOSS_RTOL


def test_judge_admits_the_argmax_and_refuses_another_token(tiny):
    from lzy_tpu.models import llama, unbox
    from lzy_tpu.models.generate import generate

    params = unbox(llama.init_params(tiny, jax.random.PRNGKey(0))[0])
    prompt = [5, 9, 3, 7]
    reply = np.asarray(generate(
        tiny, params, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=6))[0, len(prompt):].tolist()
    assert chip_smoke.greedy_gap(tiny, params, prompt, reply, 32) \
        == pytest.approx(0.0, abs=1e-5)
    wrong = list(reply)
    wrong[2] = (wrong[2] + 1) % tiny.vocab_size
    verdicts = {}
    with pytest.raises(AssertionError, match="tie tolerance"):
        chip_smoke._judge(tiny, params, "wrong", prompt, wrong, reply, 32,
                          verdicts)
    assert "parts from the reference at token 2" in verdicts["wrong"]


def test_configurations_keep_the_published_widths():
    base = LlamaConfig.llama3_8b()
    for cfg in (chip_smoke.serve_config(), chip_smoke.train_config()):
        for key in ("vocab_size", "d_model", "n_heads", "n_kv_heads", "d_ff",
                    "max_seq_len", "rope_theta"):
            assert getattr(cfg, key) == getattr(base, key), key
    assert set(chip_smoke.changed_keys(chip_smoke.serve_config())) == {
        "n_layers", "param_dtype"}
    assert set(chip_smoke.changed_keys(chip_smoke.train_config())) == {
        "n_layers", "tie_embeddings", "use_flash_kernel", "fused_ce"}


def test_main_fails_without_a_tpu_and_prints_no_result():
    """The script as the driver runs it, here where JAX finds a CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "not a TPU" in res.stderr


class TestCompileCacheHelper:
    def _run(self, env_extra):
        code = ("import json, jax\n"
                "from lzy_tpu.utils.jaxenv import CACHE_DIR, "
                "enable_compile_cache\n"
                "r = enable_compile_cache()\n"
                "print(json.dumps([r, CACHE_DIR, "
                "jax.config.jax_compilation_cache_dir]))\n")
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(env_extra, JAX_PLATFORMS="cpu")
        res = subprocess.run([sys.executable, "-c", code], env=env,
                             cwd=str(REPO), capture_output=True, text=True,
                             timeout=120)
        assert res.returncode == 0, res.stderr[-2000:]
        return json.loads(res.stdout.strip().splitlines()[-1])

    def test_a_directory_given_from_outside_stands(self, tmp_path):
        returned, _, in_effect = self._run(
            {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
        assert returned is None            # nothing was set in code
        assert in_effect == str(tmp_path)  # JAX's own reading of the variable

    def test_unset_it_is_one_fixed_path_in_the_checkout(self):
        returned, fixed, in_effect = self._run({})
        assert returned == fixed == in_effect == str(REPO / ".jax_cache")


def test_the_compile_keys_of_a_phases_line_come_from_the_programs_meter():
    """``chip_smoke.py`` has no meter of its own (ROADMAP D12): the three
    keys of its line are the build meter's totals, and they move with a
    compile."""
    import jax
    import jax.numpy as jnp

    from lzy_tpu.utils import jaxenv

    assert not hasattr(chip_smoke, "CompileMeter")
    jaxenv.install_build_meter()
    x = jnp.ones((3,))
    before = chip_smoke.compile_doc()
    assert set(before) == {"compile_seconds", "compiles", "cache_hits"}
    jax.jit(lambda v: v * 19 - 5)(x)
    after = chip_smoke.compile_doc()
    assert after["compiles"] == before["compiles"] + 1
    assert after["compile_seconds"] >= before["compile_seconds"]
    assert after["cache_hits"] - before["cache_hits"] in (0, 1)
    totals = jaxenv.build_totals()
    assert (after["compiles"], after["cache_hits"]) == (
        totals["requests"], totals["cache_hits"])
