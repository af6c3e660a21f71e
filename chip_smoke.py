#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives the two main paths once, through the entry points a user calls, at
the full width of ``llama_1b`` (and the ``resnet18_cifar`` control), with
random weights made from the config's seed:

* ``train``  — ``python -m serverless_learn_tpu train`` for a few steps of
  ResNet-18/CIFAR (SGD, 8192 samples per chip) and of Llama-1B LoRA (rank
  16, remat, 8 x 1024 tokens per chip, bf16 compute); on a host with four
  or more devices the same legs run over ``dp=N`` with ZeRO-1 and over
  ``fsdp=2,tp=2``, and the placement of parameters, optimizer state and
  batch on every device is asserted.
* ``serve``  — ``python -m serverless_learn_tpu serve --model llama_1b`` as
  a child process with the paged KV pool, prefix cache and chunked prefill
  at their defaults, answering greedy requests over the JSON-lines wire.
* ``flash``  — the default-on Pallas flash-attention kernels, forward and
  backward, compiled (never interpreted) at Llama-1B's head geometry and
  checked against dense XLA attention.

It fails — exit code other than 0, no result line — unless JAX reports a
TPU, and it never sets a platform itself. A chip belongs to one process at
a time, so this parent never imports JAX: every leg that needs the chip is
a child process, one at a time, each killed with its process group when
its time is up. The numbers printed per leg (cold first step, steady step,
request seconds) are information about the run, not benchmark results.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Each leg is a plain function of a model name and sizes, so
``tests/test_chip_smoke.py`` drives the same code on the CPU mesh at
``mlp_mnist`` / ``llama_tiny`` size with the kernels interpreted.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import queue
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
# The contract allows 1200 s, compilation included; leave a margin for the
# interpreter start-ups and the final report.
BUDGET_S = 1140.0
RESULT_TAG = "LEG_RESULT "


class LegFailed(AssertionError):
    """A leg's check did not hold; the message says which."""


def _check(cond: bool, message: str) -> None:
    if not cond:
        raise LegFailed(message)


# ---------------------------------------------------------------------------
# legs that run inside the process that holds the chip
# ---------------------------------------------------------------------------


def device_info() -> dict:
    """The device as JAX reports it, plus the versions CHANGES records."""
    import jax
    import jaxlib

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__}
    from importlib import metadata

    try:
        info["libtpu"] = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # a CPU-only installation
        info["libtpu"] = None
    return info


def _json_lines(text: str) -> List[dict]:
    out = []
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def train_leg(model: str, *, batch_per_chip: int, steps: int,
              expect_loss: float, mesh: str, optimizer: Optional[str] = None,
              lr: Optional[float] = None, seq_len: Optional[int] = None,
              sets: Sequence[str] = (),
              expect_sharded: Sequence[str] = ()) -> dict:
    """``train`` through ``cli.main`` in this process: every step's loss
    finite, the first within 15 % of what random initialisation implies
    (the synthetic batches are fresh random draws, so no falling loss is
    demanded), and ``state.step`` advanced by ``steps``. On a mesh of more
    than one device the same config's trainer is then built again and the
    placement of its state and one batch asserted."""
    from serverless_learn_tpu import cli

    n_dev = math.prod(cli._parse_mesh(mesh).values())
    argv = ["train", "--model", model, "--mesh", mesh,
            "--batch-size", str(batch_per_chip * n_dev),
            "--steps", str(steps), "-v", "--set", "train.log_every=1"]
    if optimizer:
        argv += ["--optimizer", optimizer]
    if lr is not None:
        argv += ["--lr", str(lr)]
    if seq_len is not None:
        argv += ["--seq-len", str(seq_len)]
    for item in sets:
        argv += ["--set", item]
    out, err = io.StringIO(), io.StringIO()
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = cli.main(argv)
    finally:
        sys.stderr.write(err.getvalue())
    wall = time.perf_counter() - t0
    _check(rc == 0, f"train exited {rc}")
    per_step = [r for r in _json_lines(err.getvalue())
                if "step" in r and "loss" in r]
    done = [r for r in _json_lines(out.getvalue())
            if r.get("event") == "done"]
    _check(len(done) == 1, f"expected one 'done' record, got {done}")
    _check(done[0]["final_step"] == steps,
           f"state.step is {done[0]['final_step']} after {steps} steps")
    losses = [r["loss"] for r in per_step]
    _check([r["step"] for r in per_step] == list(range(1, steps + 1)),
           f"expected a loss for each of {steps} steps, got {per_step}")
    _check(all(math.isfinite(v) for v in losses),
           f"non-finite loss in {losses}")
    _check(abs(losses[0] - expect_loss) <= 0.15 * expect_loss,
           f"first loss {losses[0]:.4f} is not within 15% of the "
           f"random-init value {expect_loss:.4f}")
    res = {"model": model, "mesh": mesh, "argv": argv[1:], "losses": losses,
           "final_step": done[0]["final_step"],
           "cold_first_step_s": per_step[0]["step_time_s"],
           "steady_step_s": min(r["step_time_s"] for r in per_step[1:]),
           "wall_s": round(wall, 2)}
    if "mfu" in done[0]:
        res["mfu_info"] = done[0]["mfu"]
    if n_dev > 1:
        res["placement"] = _placement(argv, n_dev, expect_sharded)
    return res


def _placement(argv: List[str], n_dev: int,
               expect_sharded: Sequence[str]) -> dict:
    """Build the trainer ``train`` built for ``argv`` and assert, from
    ``addressable_shards`` and per-device ``memory_stats()``, that
    parameters, optimizer state and one batch occupy all ``n_dev`` devices
    — and that the trees named in ``expect_sharded`` hold at most 60 % of
    their bytes on any one device (a replica would hold all of them)."""
    import jax

    from serverless_learn_tpu import cli
    from serverless_learn_tpu.training.loop import make_source
    from serverless_learn_tpu.training.train_step import build_trainer

    cfg = cli._trainer_config(cli.build_parser().parse_args(argv))
    trainer = build_trainer(cfg)
    _check(trainer.mesh.size == n_dev,
           f"mesh has {trainer.mesh.size} devices, wanted {n_dev}")
    state = trainer.init()
    batch = trainer.shard_batch(next(iter(make_source(cfg, trainer))))
    report, held = {}, {}
    for name, tree in (("params", state.params),
                       ("opt_state", state.opt_state), ("batch", batch)):
        per_dev: dict = {}
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            total += leaf.nbytes
            for shard in leaf.addressable_shards:
                per_dev[shard.device.id] = (per_dev.get(shard.device.id, 0)
                                            + shard.data.nbytes)
        _check(len(per_dev) == n_dev and min(per_dev.values()) > 0,
               f"{name} occupies devices {sorted(per_dev)} of {n_dev}")
        worst = max(per_dev.values())
        if name in expect_sharded:
            _check(worst <= 0.6 * total,
                   f"{name} should be sharded but one device holds "
                   f"{worst} of {total} bytes")
        for dev_id, nbytes in per_dev.items():
            held[dev_id] = held.get(dev_id, 0) + nbytes
        report[name] = {"global_bytes": total, "max_bytes_per_device": worst}
    in_use = {}
    for d in trainer.mesh.devices.flat:
        stats = d.memory_stats()  # None on the CPU backend
        if stats:
            in_use[d.id] = stats["bytes_in_use"]
            _check(stats["bytes_in_use"] >= held[d.id],
                   f"device {d.id} reports {stats['bytes_in_use']} bytes in "
                   f"use, less than the {held[d.id]} its shards hold")
    if in_use:
        report["bytes_in_use_per_device"] = in_use
    return report


def flash_leg(*, heads: int, kv_heads: int, head_dim: int, causal_len: int,
              lengths_len: int, impl: str = "auto") -> dict:
    """Flash attention forward and backward, once causal at ``causal_len``
    and once with suffix ``kv_lengths`` at ``lengths_len`` (the two
    thresholds ``auto`` switches at), against dense XLA attention on the
    same bf16 inputs. On a TPU the compiled program must contain the
    Mosaic custom call: neither the interpreter nor an XLA stand-in."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from serverless_learn_tpu.ops.attention import (dot_product_attention,
                                                    xla_attention)

    on_tpu = jax.default_backend() == "tpu"
    res = {"impl": impl, "interpret": jax.default_backend() == "cpu"}
    _check(not (on_tpu and res["interpret"]), "kernels interpreted on a TPU")

    def qkv(seed, batch, length):
        rng = jax.random.PRNGKey(seed)
        shape_q = (batch, length, heads, head_dim)
        shape_kv = (batch, length, kv_heads, head_dim)
        return (jax.random.normal(rng, shape_q, jnp.bfloat16),
                jax.random.normal(jax.random.fold_in(rng, 1), shape_kv,
                                  jnp.bfloat16),
                jax.random.normal(jax.random.fold_in(rng, 2), shape_kv,
                                  jnp.bfloat16))

    def run_case(name, q, k, v, **mask_kw):
        # Rows past their valid length carry no loss in a real batch.
        lens = mask_kw.get("kv_lengths")
        weight = (jnp.ones(q.shape[:2], jnp.float32) if lens is None else
                  (jnp.arange(q.shape[1])[None, :] < lens[:, None]
                   ).astype(jnp.float32))

        def loss(fn):
            def f(q, k, v):
                o = fn(q, k, v).astype(jnp.float32)
                return (o * o * weight[:, :, None, None]).sum(), o
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))

        def flash(q, k, v):
            return dot_product_attention(q, k, v, impl=impl, **mask_kw)

        ref_kw = dict(mask_kw)
        if lens is not None:
            del ref_kw["kv_lengths"]
            ref_kw["mask"] = (jnp.arange(k.shape[1])[None, :]
                              < lens[:, None])[:, None, None, :]

        def dense(q, k, v):
            return xla_attention(q, k, v, **ref_kw)

        kernel = loss(flash)
        t0 = time.perf_counter()
        compiled = kernel.lower(q, k, v).compile()
        compile_s = time.perf_counter() - t0
        if on_tpu:
            _check("tpu_custom_call" in compiled.as_text(),
                   f"{name}: no Mosaic custom call in the compiled program "
                   f"(auto did not choose flash, or an XLA form stood in)")
        (_, out), grads = compiled(q, k, v)
        (_, ref_out), ref_grads = loss(dense)(q, k, v)
        worst = 0.0
        pairs = [("out", out * weight[:, :, None, None],
                  ref_out * weight[:, :, None, None])]
        pairs += list(zip(("dq", "dk", "dv"), grads, ref_grads))
        for what, got, want in pairs:
            got = np.asarray(got, np.float32)
            want = np.asarray(want, np.float32)
            _check(got.shape == want.shape and np.isfinite(got).all(),
                   f"{name}: {what} has shape {got.shape} / non-finite")
            err = float(np.abs(got - want).max() / np.abs(want).max())
            # bf16 inputs and bf16 probabilities on both sides: 8 bits of
            # mantissa, accumulated over the key axis.
            _check(err < 4e-2, f"{name}: {what} differs from dense XLA "
                               f"attention by {err:.4f} of its range")
            worst = max(worst, err)
        res[name] = {"shape": list(q.shape), "compile_s": round(compile_s, 2),
                     "max_rel_err": round(worst, 5)}

    run_case("causal", *qkv(0, 1, causal_len), causal=True)
    q, k, v = qkv(1, 2, lengths_len)
    lens = jnp.asarray([lengths_len, lengths_len * 5 // 8], jnp.int32)
    run_case("kv_lengths", q, k, v, kv_lengths=lens)
    return res


def kv_pool_info(model: str, max_slots: int = 8) -> dict:
    """Bytes the serving engine's default paged KV pool takes on the
    device, measured from ``memory_stats()`` around the engine's own
    allocation, against the arithmetic of its logical shape. Information:
    TPU tiling can pad small minor dimensions."""
    import jax

    from serverless_learn_tpu.config import KVCacheConfig
    from serverless_learn_tpu.inference import kvcache
    from serverless_learn_tpu.inference.generate import init_cache
    from serverless_learn_tpu.models.registry import get_model

    module = get_model(model).module
    kv = KVCacheConfig()
    max_pages = kvcache.pages_for(module.cfg.max_seq_len, kv.block_size)
    num_blocks = max_slots * max_pages + max_pages  # the engine's default
    pmod = kvcache.paged_module(module, kv.block_size, num_blocks)
    dev = jax.local_devices()[0]
    before = (dev.memory_stats() or {}).get("bytes_in_use")
    pages, _ = kvcache.split_cache(init_cache(pmod, max_slots))
    jax.block_until_ready(pages)
    after = (dev.memory_stats() or {}).get("bytes_in_use")
    logical = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(pages))
    leaf = jax.tree_util.tree_leaves(pages)[0]
    return {"model": model, "num_blocks": num_blocks,
            "page_shape": list(leaf.shape), "dtype": str(leaf.dtype),
            "arithmetic_bytes": logical,
            "measured_bytes": (after - before
                               if before is not None else None)}


# ---------------------------------------------------------------------------
# the serve leg: a child server, this process is the wire client
# ---------------------------------------------------------------------------


def _wire(addr: str, req: dict, timeout_s: float) -> dict:
    host, _, port = addr.rpartition(":")
    with socket.create_connection((host, int(port)), timeout=timeout_s) as s:
        with s.makefile("rwb") as f:
            f.write(json.dumps(req).encode() + b"\n")
            f.flush()
            line = f.readline()
    _check(bool(line), "server closed the connection without a reply")
    return json.loads(line)


def _stop_group(proc: subprocess.Popen, grace_s: float = 30.0) -> int:
    """SIGINT (the server's clean-shutdown path), then the whole process
    group is killed: nothing this script started outlives it."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGINT)
            proc.wait(timeout=grace_s)
        except (subprocess.TimeoutExpired, ProcessLookupError):
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    return proc.wait()


@contextlib.contextmanager
def _sigint_not_ignored():
    """A child inherits an IGNORED SIGINT (which a parent started in the
    background of a non-interactive shell has), and then never sees the
    KeyboardInterrupt that is the server's clean-shutdown path. Handlers
    are reset by exec, so a default disposition while the child starts is
    all it takes."""
    previous = signal.signal(signal.SIGINT, signal.SIG_DFL)
    try:
        yield
    finally:
        signal.signal(signal.SIGINT, previous)


def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    return env


def serve_leg(model: str, *, vocab: int, long_prompt: int, long_new: int,
              shared_prefix: int, timeout_s: float = 900.0,
              log_path: Optional[str] = None) -> dict:
    """``serve`` as a child process on one device, engine options at their
    defaults, and a handful of greedy requests over the JSON-lines wire:
    one short; one long enough for several prefill chunks that crosses a
    table-window bucket during decode; two that share a prefix (the trie
    must report a hit); the short one again (identical tokens). Every
    reply carries the tokens asked for, ids inside the vocabulary and no
    ``"error"`` key — the dispatcher answers a failed compile with one and
    keeps serving, so only the replies can tell."""
    from serverless_learn_tpu.config import KVCacheConfig  # jax-free

    block_size = KVCacheConfig().block_size
    _check(long_prompt > 2 * KVCacheConfig().prefill_chunk,
           "the long prompt must need several prefill chunks")
    argv = [sys.executable, "-m", "serverless_learn_tpu", "serve",
            "--model", model, "--port", "0", "--mesh", "dp=1"]
    deadline = time.monotonic() + timeout_s
    log = open(log_path, "w") if log_path else subprocess.DEVNULL
    with _sigint_not_ignored():
        proc = subprocess.Popen(argv, stdout=subprocess.PIPE, stderr=log,
                                cwd=HERE, env=_child_env(), text=True,
                                start_new_session=True)
    lines: queue.Queue = queue.Queue()
    threading.Thread(target=lambda: [lines.put(l) for l in proc.stdout],
                     daemon=True).start()
    res: dict = {"model": model, "requests": []}
    try:
        t0 = time.monotonic()
        addr = None
        while addr is None:
            _check(proc.poll() is None,
                   f"serve exited {proc.returncode} before it listened")
            _check(time.monotonic() < deadline, "serve never listened")
            try:
                rec = _json_lines(lines.get(timeout=1.0))
            except queue.Empty:
                continue
            if rec and rec[0].get("event") == "serving":
                addr = rec[0]["addr"]
        res["startup_s"] = round(time.monotonic() - t0, 2)

        def ask(name: str, prompt: List[int], max_new: int) -> List[int]:
            t = time.monotonic()
            rep = _wire(addr, {"prompt": prompt, "max_new_tokens": max_new,
                               "temperature": 0.0},
                        timeout_s=max(1.0, deadline - time.monotonic()))
            _check("error" not in rep, f"{name}: server replied {rep}")
            new = rep["new_tokens"]
            _check(len(new) == max_new,
                   f"{name}: {len(new)} tokens for {max_new} asked")
            _check(all(isinstance(t_, int) and 0 <= t_ < vocab
                       for t_ in new), f"{name}: token outside the vocabulary")
            _check(rep["tokens"] == prompt + new,
                   f"{name}: reply does not echo the prompt")
            res["requests"].append(
                {"name": name, "prompt_tokens": len(prompt),
                 "new_tokens": max_new,
                 "seconds": round(time.monotonic() - t, 2)})
            return new

        def tokens(seed: int, n: int) -> List[int]:
            return [(seed * 7919 + i * 104729) % vocab for i in range(n)]

        def ping() -> dict:
            return _wire(addr, {"op": "ping"}, timeout_s=30.0)

        short = tokens(1, 8)
        first = ask("short", short, 8)
        # Decode must start inside one power-of-four table-window bucket
        # and end in the next (continuous._wbucket).
        pages0 = -(-(long_prompt + 1) // block_size)
        pages1 = -(-(long_prompt + long_new) // block_size)
        bucket = 1
        while bucket < pages0:
            bucket *= 4
        _check(pages1 > bucket, "the long request must cross a table-window "
                                "bucket while decoding")
        ask("long", tokens(2, long_prompt), long_new)
        prefix = tokens(3, shared_prefix)
        ask("prefix_a", prefix + tokens(4, 8), 8)
        hits_before = ping()["kv"]
        ask("prefix_b", prefix + tokens(5, 8), 8)
        kv = ping()["kv"]
        _check(kv["prefix_hit_rate_lifetime"]
               > hits_before["prefix_hit_rate_lifetime"]
               and kv["prefix_blocks_cached"] >= shared_prefix // block_size,
               f"the prefix trie reported no hit for a shared "
               f"{shared_prefix}-token prefix: {kv}")
        again = ask("short_again", short, 8)
        _check(again == first, f"the repeated request answered {again}, "
                               f"first {first}")
        # The fastest request found every bucket it needed compiled.
        res["steady_request_s"] = min(r["seconds"] for r in res["requests"])
        res["kv"] = {k: kv[k] for k in ("blocks_total", "blocks_free",
                                        "prefix_hit_rate_lifetime",
                                        "prefix_blocks_cached",
                                        "preemptions")}
    finally:
        rc = _stop_group(proc)
        if log_path:
            log.close()
    _check(rc == 0, f"serve exited {rc} after SIGINT")
    return res


# ---------------------------------------------------------------------------
# the plan and the parent
# ---------------------------------------------------------------------------


def _plan(n_dev: int) -> List[tuple]:
    """(leg name, function name, keyword arguments), in order."""
    if n_dev >= 4:
        # What bench.py selects on several devices, and the model-sharded
        # layout a 1B+ state needs.
        resnet = dict(mesh=f"dp={n_dev}", sets=["train.zero_stage=1"],
                      expect_sharded=["opt_state", "batch"])
        llama = dict(mesh=f"dp={n_dev // 4},fsdp=2,tp=2",
                     expect_sharded=["params", "batch"])
    else:
        resnet = dict(mesh=f"dp={n_dev}")
        llama = dict(mesh=f"dp={n_dev}")
    return [
        ("train_resnet18", "train_leg", dict(
            model="resnet18_cifar", batch_per_chip=8192, steps=4,
            expect_loss=math.log(10), optimizer="sgd", lr=0.1, **resnet)),
        ("train_llama1b_lora", "train_leg", dict(
            model="llama_1b", batch_per_chip=8, seq_len=1024, steps=3,
            expect_loss=math.log(128256),
            sets=["model_overrides.lora_rank=16", "train.remat=true",
                  "train.dtype=bfloat16"], **llama)),
        ("serve_llama1b", "serve_leg", dict(
            model="llama_1b", vocab=128256, long_prompt=200, long_new=96,
            shared_prefix=64)),
        ("flash_llama1b_heads", "flash_leg", dict(
            heads=32, kv_heads=8, head_dim=64, causal_len=4096,
            lengths_len=512)),
        ("kv_pool_llama1b", "kv_pool_info", dict(model="llama_1b")),
    ]


LEGS = {f.__name__: f for f in (device_info, train_leg, flash_leg,
                                kv_pool_info, serve_leg)}


def _run_in_child(func: str, kwargs: dict, timeout_s: float,
                  log_path: str) -> dict:
    """Run one leg in a fresh process (it holds the chip alone) and return
    the result it printed; its stderr goes to ``log_path``."""
    argv = [sys.executable, os.path.join(HERE, "chip_smoke.py"),
            "--leg", func, "--kwargs", json.dumps(kwargs)]
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, stdout=subprocess.PIPE, stderr=log,
                                cwd=HERE, env=_child_env(), text=True,
                                start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise LegFailed(f"no result within {timeout_s:.0f} s")
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    tagged = [l for l in out.splitlines() if l.startswith(RESULT_TAG)]
    if proc.returncode != 0 or not tagged:
        with open(log_path) as f:
            tail = f.read()[-3000:]
        raise LegFailed(f"exit code {proc.returncode}\n{tail}")
    return json.loads(tagged[-1][len(RESULT_TAG):])


def _leg_main(func: str, kwargs: dict) -> int:
    """``--leg``: run one leg in this process and print its result."""
    try:
        result = LEGS[func](**kwargs)
    except LegFailed as e:
        print(f"chip_smoke leg {func} failed: {e}", file=sys.stderr)
        return 1
    print(RESULT_TAG + json.dumps(result), flush=True)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leg", choices=sorted(LEGS), help=argparse.SUPPRESS)
    ap.add_argument("--kwargs", default="{}", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "serverless_learn_tpu")):
        print("chip_smoke: the serverless_learn_tpu package is not beside "
              "this script; run it from a checkout of the repo",
              file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    from serverless_learn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()  # before any JAX import; children inherit it
    if args.leg:
        return _leg_main(args.leg, json.loads(args.kwargs))

    t_start = time.monotonic()
    log_dir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(log_dir, exist_ok=True)

    def remaining() -> float:
        return BUDGET_S - (time.monotonic() - t_start)

    try:
        dev = _run_in_child("device_info", {}, min(300.0, remaining()),
                            os.path.join(log_dir, "device_info.log"))
    except LegFailed as e:
        print(f"chip_smoke: JAX found no usable device: {e}", file=sys.stderr)
        return 2
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev['platform']!r} ({dev['count']} x {dev['kind']}); "
              f"no leg was run", file=sys.stderr)
        return 2
    print(json.dumps({"leg": "device_info", **dev,
                      "compile_cache": os.environ.get(
                          "JAX_COMPILATION_CACHE_DIR")}), flush=True)
    failed = []
    for name, func, kwargs in _plan(dev["count"]):
        log_path = os.path.join(log_dir, f"{name}.log")
        t0 = time.monotonic()
        try:
            _check(remaining() > 30.0, "the 1200 s budget is spent")
            if func == "serve_leg":
                # The wire client touches no JAX, so it runs here; the
                # server is the child that holds the chip.
                result = serve_leg(timeout_s=remaining() - 20.0,
                                   log_path=log_path, **kwargs)
            else:
                result = _run_in_child(func, kwargs, remaining() - 10.0,
                                       log_path)
            print(json.dumps({"leg": name, "ok": True,
                              "leg_wall_s": round(time.monotonic() - t0, 1),
                              **result}), flush=True)
        except LegFailed as e:
            failed.append(name)
            print(json.dumps({"leg": name, "ok": False,
                              "leg_wall_s": round(time.monotonic() - t0, 1),
                              "error": str(e)}), flush=True)
    total = round(time.monotonic() - t_start, 1)
    if failed:
        print(json.dumps({"ok": False, "failed": failed, "wall_s": total}),
              flush=True)
        return 1
    print(json.dumps({"leg": "total", "wall_s": total}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
