"""The GLM-4.7-Flash cell's benchmark pieces inside the tier-1 gate
(``benchmark/tests`` is collected by hand only): the operation and byte
counts against hand counts, the new readers over a scoped trace, the toy
rehearsal of the ``serve_routed`` cell, and ``correct`` false under the fp8
control and under each planted fault."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests.test_glm47flash import (  # noqa: E402,F401
    glm_root,
    test_a_pass_hands_back_the_sets_it_used,
    test_altered_token_comes_out_not_correct,
    test_attention_and_expert_work,
    test_control_fp8_reads_above_the_limit,
    test_names_are_the_programs_expert_scopes,
    test_parameters_at_the_published_sizes,
    test_readers_read_the_expert_and_latent_scopes,
    test_readers_return_nothing_for_a_program_without_the_scopes,
    test_reference_refuses_an_illegitimate_route,
    test_routed_driver_runs_the_cell,
    test_selection_without_the_bias_comes_out_not_correct,
    test_swapped_expert_comes_out_not_correct,
    test_unnormalised_gates_come_out_not_correct,
)
