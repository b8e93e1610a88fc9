"""Test configuration: an 8-device virtual CPU mesh, so multi-chip
sharding paths are exercised without TPU hardware. The config values are
set here, before any backend starts, so the suite runs the same way
whatever JAX_PLATFORMS / XLA_FLAGS the caller's shell carries; the
environment variable hands the same eight devices to the children that
tests spawn.
"""
import os

import jax
import pytest

os.environ.setdefault("JAX_NUM_CPU_DEVICES", "8")
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# -- the `quick` tier (pytest -m quick): one representative test per
# subsystem, kept under 2 minutes total, so a fast green bar exists
# between full runs (~4 min with six workers). Centralized here instead
# of scattering @pytest.mark.quick decorators: the tier is a curated LIST,
# and curating it in one place keeps the runtime budget reviewable.
_QUICK = {
    "test_ndarray.py::test_arithmetic_broadcast",
    "test_ndarray.py::test_csr_duplicate_entries_canonicalized",
    "test_symbol.py::test_infer_shape_conv_net",
    "test_operator.py::test_convolution",
    "test_op_gradients.py::test_binary_gradient",
    "test_autograd.py::test_chain_and_broadcast_backward",
    "test_module.py::test_module_fit_mlp_converges",
    "test_module_family.py::test_group2ctx_executes",
    "test_multistep.py::test_step_k_matches_sequential",
    "test_segmented_mp.py::test_stage_placement",
    "test_gluon.py::test_dense_eager_hybrid_match",
    "test_gluon.py::test_dataloader_process_workers_match_threads",
    "test_io.py::test_ndarray_iter_basic",
    "test_native.py::test_uint8_output_mode_matches_f32",
    "test_optimizer.py::test_sgd_mom_update_op",
    "test_metric.py::test_accuracy",
    "test_kvstore.py::test_aggregator_multi_device",
    "test_kvstore.py::test_async_sync_fallback_warns",
    "test_parallel.py::test_build_mesh",
    "test_parallel.py::test_dp_matches_single_device",
    "test_attention.py::test_flash_kernel_single_and_multi_block",
    "test_sp.py::test_ring_attention_matches_dense",
    "test_rnn.py::test_rnn_cell_unroll_shapes",
    "test_container.py::"
    "test_written_file_is_byte_identical_to_reference_layout",
    "test_legacy_json.py::test_reference_v1_json_loads_and_binds",
    "test_model_store.py::test_verified_cache_hit",
    "test_export_predictor.py::test_predictor_contract",
    "test_feedforward.py::test_feedforward_predict_return_data",
    "test_quantization.py::test_quantize_dequantize_roundtrip",
    "test_sparse_optimizer.py::test_sgd_lazy_update_touches_only_grad_rows",
    "test_image.py::test_crops_and_normalize",
    "test_profiler.py::test_print_summary",
    "test_pipeline.py::test_feed_order_values_and_shutdown",
    "test_pipeline.py::test_module_fit_bit_identical_with_feed",
    "test_amp.py::test_amp_bf16_mlp_converges_with_f32_masters",
    "test_amp.py::test_fp16_scaler_skips_step_and_halves_scale",
    "test_checkpoint.py::test_atomic_commit_roundtrip",
    "test_checkpoint.py::test_module_fit_resume_bit_identical",
    "test_checkpoint.py::test_sharded_split0_and_whole_placement",
    "test_telemetry.py::test_registry_absorbs_profiler_hooks_and_dedups",
    "test_telemetry.py::test_exporter_scrape_during_live_fit",
    "test_telemetry.py::test_watchdog_stall_dump_and_rearm",
    "test_tracing.py::test_span_nesting_and_thread_stacks",
    "test_tracing.py::test_event_ring_bound_and_drop_accounting",
    "test_tracing.py::test_merge_aligns_clocks_and_names_victims",
    "test_tracing.py::test_merge_survives_missing_and_torn_shards",
    "test_devstats.py::test_preflight_accept_reject_boundaries",
    "test_devstats.py::test_recompile_sentinel_threshold",
    "test_devstats.py::test_mfu_and_roofline_arithmetic",
    "test_devstats.py::test_serving_resident_bytes_accounting_across_admits",
    "test_tracing.py::test_steplog_phase_fields_and_overlap_fracs",
    "test_tracing.py::test_flightrec_ring_dump_and_tail",
    "test_zero.py::test_zero1_fp32_bit_identical",
    "test_zero.py::test_resume_across_stage_change",
    "test_embedding.py::test_rows_adam_matches_dense_restricted",
    "test_embedding.py::test_kvstore_row_sparse_pull_edge_cases",
    "test_embedding.py::test_sparse_dense_bit_identity_all_rows_touched",
    "test_frontend.py::test_router_lru_eviction_order_by_resident_bytes",
    "test_frontend.py::"
    "test_preflight_rejected_load_leaves_router_state_unchanged",
    "test_frontend.py::test_least_loaded_dispatch_picks_idle_replica",
    "test_frontend.py::test_admission_class_shed_ordering",
    "test_frontend.py::test_http_status_mapping",
    "test_decode.py::test_decode_matches_full_context_recompute",
    "test_decode.py::test_pool_full_admission_is_sized_507",
    "test_decode.py::test_quantized_matmul_matches_dequant_then_matmul",
    "test_supervisor.py::test_decide_transient_restarts_in_place",
    "test_supervisor.py::test_decide_crash_loop_gives_up",
    "test_supervisor.py::test_run_repeat_offender_shrinks_then_finishes",
    "test_supervisor.py::test_run_budget_exhaustion_gives_up_44",
    "test_supervisor.py::test_parse_host_spec_round_trip",
    "test_supervisor.py::test_ssh_transport_command_env_contract",
    "test_cluster.py::test_quiet_rank_tie_breaks_on_last_sequence_number",
    "test_analysis.py::test_repo_is_clean_under_strict",
    "test_analysis.py::test_amp_wire_invariant_via_auditor",
    "test_analysis.py::test_tracelint_item_sync_in_scanned_step",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        base = item.nodeid.split("/")[-1]
        # strip parametrization: tier membership is per test function
        fn = base.split("[")[0]
        if fn in _QUICK:
            item.add_marker(pytest.mark.quick)
