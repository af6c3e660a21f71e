"""Test harness: 8 virtual CPU devices stand in for a TPU slice.

The reference's only "test rig" was three localhost processes simulating a
cluster (SURVEY.md §4). The JAX-idiomatic equivalent is
``--xla_force_host_platform_device_count``: one process, eight devices, real
Mesh/collective semantics. Must run before jax is imported anywhere.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import pytest  # noqa: E402

# -- runtime lock-order detection (SLT_LOCKCHECK=1) --------------------------
#
# The dynamic half of `slt check`'s SLT001: instrument every lock the
# package creates, record real acquisition orderings across the whole
# suite, and fail the session on cycles (analysis/lockcheck.py). Installed
# HERE — before any serverless_learn_tpu module runs its module-level
# `threading.Lock()` — and scoped to locks created from this repo's files.

_LOCKCHECK = os.environ.get("SLT_LOCKCHECK", "") == "1"
if _LOCKCHECK:
    from serverless_learn_tpu.analysis import lockcheck as _lockcheck

    _lockcheck.install()

# -- runtime happens-before race detection (SLT_RACECHECK=1) ------------------
#
# The dynamic half of SLT007 (analysis/racecheck.py): vector clocks over
# lock acquire/release, Thread start/join and queue/Event handoffs, plus
# sampled attribute-write instrumentation on the fleet/gossip/kvcache/
# health classes. Unordered write/write (and, with SLT_RACECHECK_READS=1,
# read/write) pairs fail the session with both stacks.

_RACECHECK = os.environ.get("SLT_RACECHECK", "") == "1"
if _RACECHECK:
    from serverless_learn_tpu.analysis import racecheck as _racecheck

    _racecheck.install()

# -- runtime compile monitoring (SLT_JITCHECK=1) -------------------------------
#
# The dynamic half of SLT010-SLT013 (analysis/jitcheck.py): wrap every
# jax.jit the package creates, record real compilations (site, abstract
# shapes, donation mask, elapsed), enforce the per-site compile budgets
# declared next to the bucket functions, and detect donated-buffer reuse
# logically (the round-15 "Array has been deleted" class — caught on CPU
# where donation is otherwise a silent no-op). Installed HERE, before
# any `@jax.jit` decorator binds at package import. Budget/frozen/reuse
# violations fail the session below (exit 5; lockcheck=3, racecheck=4).

_JITCHECK = os.environ.get("SLT_JITCHECK", "") == "1"
if _JITCHECK:
    from serverless_learn_tpu.analysis import jitcheck as _jitcheck

    _jitcheck.install()


def pytest_sessionfinish(session, exitstatus):
    if _JITCHECK:
        jmon = _jitcheck.monitor()
        print(f"\n{jmon.report()}")
        jmon.close_log()
        if jmon.violations():
            pytest.exit("jitcheck: compile-budget/frozen-window/"
                        "donation violations observed (see report "
                        "above)", returncode=5)
    if _RACECHECK:
        rmon = _racecheck.monitor()
        print(f"\n{rmon.report()}")
        rmon.close_log()
        if rmon.races():
            pytest.exit("racecheck: unordered conflicting accesses "
                        "observed (see report above)", returncode=4)
    if not _LOCKCHECK:
        return
    mon = _lockcheck.monitor()
    rep = mon.report()
    print(f"\n{rep}")
    if mon.violations():
        # pytest.exit with a returncode is the one channel wrap_session
        # honors from inside this hook (assigning session.exitstatus here
        # is discarded).
        pytest.exit("lockcheck: lock-order cycle observed (see report "
                    "above)", returncode=3)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _reset_active_mesh():
    """The active mesh is process-global (set by build_trainer); a test that
    builds a trainer must not leak it into the next test — a stale mesh
    silently reroutes the pallas ops' mesh-aware dispatch (e.g. flash
    falling back to dense for batch-indivisibility against a mesh the test
    never asked for)."""
    yield
    from serverless_learn_tpu.parallel.ring_attention import set_active_mesh

    set_active_mesh(None)


# -- fast/slow tiers ---------------------------------------------------------
#
# The full suite takes ~13 min on the 8-device CPU mesh (VERDICT round 1:
# "split the suite so rounds 2+ can actually run it"). Tests measured >=3 s
# are tier "slow"; `make test` runs the fast tier (<2 min), `make test-all`
# runs everything. Node ids, not file-level marks, so every subsystem keeps
# fast-tier coverage. Re-measure with `pytest --durations=0` when adding
# compile-heavy tests.

SLOW_TESTS = {
    "tests/test_checkpoint.py::test_checkpoint_via_shard_server",
    "tests/test_checkpoint_sharded.py::test_save_dp_restore_fsdp_tp_bit_exact",
    "tests/test_checkpoint_sharded.py::test_restore_fetches_ranges_not_blobs",
    "tests/test_checkpoint_sharded.py::test_bf16_leaves_roundtrip",
    "tests/test_checkpoint_sharded.py::test_latest_gc_and_layout_autodetect",
    "tests/test_checkpoint_sharded.py::test_sharded_checkpoint_via_shard_server",
    "tests/test_checkpoint.py::test_latest_and_gc",
    "tests/test_checkpoint.py::test_resume_is_exact",
    "tests/test_cli.py::test_publish_stats_and_train_from_shard_server",
    "tests/test_real_data.py::test_cifar_bytes_to_rising_accuracy",
    "tests/test_real_data.py::test_corpus_to_bert_mlm_training",
    "tests/test_cli.py::test_train_end_to_end",
    "tests/test_configs.py::test_small_rungs_build[cifar_resnet18_dp4.json]",
    "tests/test_configs.py::test_small_rungs_build[mnist_mlp.json]",
    "tests/test_elastic.py::test_join_grows_mesh_and_crash_shrinks_it",
    "tests/test_elastic.py::test_solo_run_without_coordinator",
    "tests/test_elastic.py::test_state_survives_remesh_exactly",
    "tests/test_elastic_shard_data.py::test_elastic_worker_streams_from_shard_server",
    "tests/test_flash_attention.py::test_flash_inside_pipeline_stage",
    "tests/test_flash_masks.py::test_bert_step_executes_flash_path",
    "tests/test_flash_attention.py::test_flash_sharded_train_step_matches_xla[mesh_kw0]",
    "tests/test_flash_attention.py::test_flash_sharded_train_step_matches_xla[mesh_kw1]",
    "tests/test_flash_attention.py::test_transformer_with_flash_impl",
    "tests/test_fused_ce.py::test_bf16_logits",
    "tests/test_fused_ce.py::test_fused_train_step_matches_unfused",
    "tests/test_fused_ce.py::test_matches_optax_forward_and_grad[shape0-512]",
    "tests/test_fused_ce.py::test_matches_optax_forward_and_grad[shape1-1024]",
    "tests/test_fused_ce.py::test_matches_optax_forward_and_grad[shape2-512]",
    "tests/test_generate.py::test_decode_matches_full_forward",
    "tests/test_generate.py::test_eos_is_sticky",
    "tests/test_generate.py::test_greedy_generation_matches_full_forward_argmax",
    "tests/test_grad_accum_eval.py::test_grad_accum_matches_whole_batch",
    "tests/test_grad_accum_eval.py::test_grad_accum_sharded_transformer_runs",
    "tests/test_grad_accum_eval.py::test_in_loop_eval_fires",
    "tests/test_grad_accum_eval.py::test_mlm_grad_accum_matches_whole_batch",
    "tests/test_grad_accum_eval.py::test_resnet_eval_uses_running_stats_and_keeps_state",
    "tests/test_grad_accum_eval.py::test_run_eval_mean_metrics",
    "tests/test_grad_accum_eval.py::test_run_eval_streams_from_shard_server",
    "tests/test_local_sgd.py::test_replicas_diverge_then_gossip_reconverges",
    "tests/test_local_sgd.py::test_run_local_sgd_integrated_with_checkpoint",
    "tests/test_moe.py::test_moe_aux_loss_reported",
    "tests/test_moe.py::test_moe_group_size_bounds_capacity_without_changing_math",
    "tests/test_moe.py::test_moe_init_state_has_no_losses_collection",
    "tests/test_moe.py::test_moe_layer_matches_manual_dense_top1",
    "tests/test_moe.py::test_moe_trains_ep_matches_dp[mesh_cfg0]",
    "tests/test_moe.py::test_moe_trains_ep_matches_dp[mesh_cfg1]",
    "tests/test_moe.py::test_n_experts_override_keeps_aux_loss",
    "tests/test_multihost.py::test_two_process_training",
    "tests/test_optimizers.py::test_lr_reported_in_metrics",
    "tests/test_optimizers.py::test_optimizer_reduces_loss_on_fixed_batch[adafactor]",
    "tests/test_optimizers.py::test_optimizer_reduces_loss_on_fixed_batch[adam]",
    "tests/test_optimizers.py::test_optimizer_reduces_loss_on_fixed_batch[adamw]",
    "tests/test_optimizers.py::test_optimizer_reduces_loss_on_fixed_batch[lion]",
    "tests/test_optimizers.py::test_optimizer_reduces_loss_on_fixed_batch[rmsprop]",
    "tests/test_optimizers.py::test_optimizer_reduces_loss_on_fixed_batch[sgd]",
    "tests/test_pipeline.py::test_gpipe_matches_sequential_forward",
    "tests/test_pipeline.py::test_pipelined_train_step_matches_dp",
    "tests/test_pipeline.py::test_pp_tp_train_step_matches_dp",
    "tests/test_pipeline.py::test_interleaved_schedule_matches_dp",
    "tests/test_pipeline.py::test_interleaved_toy_matches_permuted_sequential",
    "tests/test_ring_attention.py::test_llama_trains_with_sp_axis",
    "tests/test_ring_attention.py::test_ring_flash_hops_selected_and_match",
    "tests/test_ring_attention.py::test_ring_flash_hops_gqa_unexpanded",
    "tests/test_ring_attention.py::test_ring_flash_hops_noncausal_grad",
    "tests/test_ring_attention.py::test_ring_grad_matches_dense",
    "tests/test_ring_attention.py::test_ring_matches_dense_gqa",
    "tests/test_serve.py::test_serve_matches_direct_generate",
    "tests/test_serve.py::test_serve_survives_malformed_json_values",
    "tests/test_shard_datasets.py::test_publish_from_bundle_and_training",
    "tests/test_tracing.py::test_training_records_step_spans",
    "tests/test_train_step.py::test_bert_tiny_mlm_step",
    "tests/test_train_step.py::test_dp8_matches_single_device_exactly",
    "tests/test_train_step.py::test_dp_tp_matches_dp_only",
    "tests/test_train_step.py::test_llama_lora_freezes_base",
    "tests/test_train_step.py::test_llama_tiny_fsdp_tp",
    "tests/test_train_step.py::test_mlp_overfits_fixed_batch_single_device",
    "tests/test_train_step.py::test_remat_matches_no_remat",
    "tests/test_train_step.py::test_resnet18_step_runs_and_updates_batchstats",
    "tests/test_train_step.py::test_train_dtype_policy_reaches_model",
    # round 4
    "tests/test_pipeline.py::test_pp_sp_train_step_matches_dp",
    "tests/test_pipeline.py::test_pp_sp_suffix_lengths_match_dp",
    "tests/test_pipeline.py::test_pp_ep_train_step_matches_dp",
    "tests/test_pipeline.py::test_pp_tp_moe_train_step_matches_dp",
    "tests/test_pipeline.py::test_moe_pipeline_matches_dp",
    "tests/test_local_sgd.py::test_stateful_resnet_gossip_trains_and_stats_gossip",
    "tests/test_local_sgd.py::test_stateful_diloco_exact_parity_groupnorm",
    "tests/test_local_sgd.py::test_stateful_diloco_batchnorm_tolerance_documented",
    "tests/test_continuous.py::test_padded_batch_generate_matches_solo",
    "tests/test_parallel_ingest.py::test_resnet50_device_augment_trains",
    "tests/test_tokenizer.py::test_packed_batches_train_llama_and_bert",
    "tests/test_flash_masks.py::test_dispatcher_honors_kv_lengths_alone",
    # round 5
    "tests/test_continuous.py::test_concurrent_greedy_exact",
    "tests/test_continuous.py::test_mid_stream_admission_exact",
    "tests/test_continuous.py::test_eos_retires_slot_early",
    "tests/test_continuous.py::test_more_requests_than_slots",
    "tests/test_continuous.py::test_mixed_sampling_in_one_batch_no_starvation",
    "tests/test_continuous.py::test_sampled_is_reproducible_and_batch_invariant",
    "tests/test_continuous.py::test_server_with_continuous_engine",
    "tests/test_moe_generate.py::test_moe_through_continuous_engine",
    "tests/test_moe_generate.py::test_moe_serves_over_the_wire",
    "tests/test_moe_generate.py::test_moe_batched_padded_prompts_match_solo",
    "tests/test_diloco_dcn.py::test_two_islands_converge_and_track_single_world",
    "tests/test_diloco_dcn.py::test_island_crash_does_not_wedge_survivors",
    "tests/test_diloco_dcn.py::test_leader_crash_hands_over",
    "tests/test_diloco_dcn.py::test_late_joiner_adopts_current_anchor",
    "tests/test_diloco_dcn.py::test_islands_are_sharded_worlds",
    # round 19 (real-daemon DiLoCo quorum integration; the jit-free gate
    # units and the vmapped herd acceptance stay fast)
    "tests/test_diloco_dcn.py::test_quorum_closes_round_without_straggler",
    "tests/test_speculative.py::test_cross_draft_is_exact",
    "tests/test_speculative.py::test_self_draft_is_exact_and_fully_accepted",
    "tests/test_speculative.py::test_unequal_prompts_exact",
    "tests/test_qlora.py::test_int8_frozen_base_trains_lora",
    "tests/test_qlora.py::test_qlora_lora_grads_track_bf16_base_grads",
    "tests/test_quantize.py::test_quant_moe_experts",
    # round 9 (goodput acceptance: a real train run through the ledger)
    "tests/test_goodput.py::test_train_run_records_goodput",
    # round 13 (paged KV: model-backed equivalence suite; the jax-free
    # allocator/trie/doctor units stay fast)
    "tests/test_kvcache.py::test_paged_generate_matches_monolithic",
    "tests/test_kvcache.py::test_paged_engine_greedy_exact_with_chunked_prefill",
    "tests/test_kvcache.py::test_seeded_sampling_is_blind_to_page_and_chunk_size",
    "tests/test_kvcache.py::test_paged_engine_eos_retires_and_frees_blocks",
    "tests/test_kvcache.py::test_shared_prefix_reuse_hits_and_stays_exact",
    "tests/test_kvcache.py::test_exhaustion_backpressure_and_preemption_stay_exact",
    "tests/test_kvcache.py::test_decode_cost_tracks_live_slots",
    "tests/test_kvcache.py::test_server_ping_reports_kv_and_prompt_histogram",
    # round 6 (telemetry integration; registry/endpoint/top units stay fast)
    "tests/test_telemetry.py::test_server_metrics_endpoint_scrape",
    "tests/test_telemetry.py::test_continuous_cancellation_retires_slot",
    "tests/test_telemetry.py::test_top_once_covers_trainer_and_inference",
    # round 17 (numerics: real-trainer fingerprint runs + the cadence/
    # overhead acceptance run; the stat/detector/provenance units stay
    # fast)
    "tests/test_numerics.py::test_fingerprint_bisection_finds_seeded_divergence",
    "tests/test_numerics.py::test_numerics_cadence_and_overhead_acceptance",
    # round 18 (ZeRO: the adafactor parity variant pays a second pair of
    # trainer compiles; the adamw variant and the mlp parity/layout/
    # checkpoint/elastic tests stay in the fast tier)
    "tests/test_optimizers.py::test_zero1_update_matches_replicated[adafactor]",
    # round 25 (jitcheck: the engine+trainer acceptance run pays real
    # compiles, and the no-baseline HEAD scan duplicates the full-repo
    # walk test_analysis already pays once; the rule fixtures, monitor
    # units and subprocess session-failure tests stay fast)
    "tests/test_jitcheck.py::"
    "test_warmed_engine_and_train_loop_have_no_unexpected_compiles",
    "tests/test_jitcheck.py::test_repo_at_head_is_clean_for_new_rules",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: compile-heavy test (excluded from `make test`)")


def pytest_collection_modifyitems(config, items):
    for item in items:
        nodeid = item.nodeid.replace("\\", "/")
        # A bare (un-parametrized) entry in SLOW_TESTS marks every
        # parametrization of that test.
        if nodeid in SLOW_TESTS or nodeid.split("[")[0] in SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
