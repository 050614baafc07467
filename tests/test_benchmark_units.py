"""The yardstick's own unit tests, collected by tier-1: the statistics,
the FLOP count, the load generator, the trace reductions, the readers of the
program's record of its requests and the float32 references of `benchmark/`.
The bodies live in `benchmark/tests/`; the cell rehearsals there spawn child
runs and stay by hand."""
import pytest

pytest.register_assert_rewrite(
    "benchmark.tests.test_yardstick", "benchmark.tests.test_program_spans",
    "benchmark.tests.test_moe_roofline", "benchmark.tests.test_reference",
    "benchmark.tests.test_moe_share_roofline",
    "benchmark.tests.test_conv_kinds", "benchmark.tests.test_hc_kinds",
    "benchmark.tests.test_ssm_kinds", "benchmark.tests.test_ssm_roofline",
    "benchmark.tests.test_ssd_kinds", "benchmark.tests.test_ssd_roofline",
    "benchmark.tests.test_request_timeline",
    "benchmark.tests.test_kda_kinds", "benchmark.tests.test_kda_roofline",
    "benchmark.tests.test_reference_kimi_linear",
    "benchmark.tests.test_gdn_kinds", "benchmark.tests.test_gdn_roofline",
    "benchmark.tests.test_reference_qwen3_next")

from benchmark.tests.test_conv_kinds import *  # noqa: E402,F401,F403
from benchmark.tests.test_gdn_kinds import *  # noqa: E402,F401,F403
from benchmark.tests.test_gdn_roofline import *  # noqa: E402,F401,F403
from benchmark.tests.test_hc_kinds import *  # noqa: E402,F401,F403
from benchmark.tests.test_kda_kinds import *  # noqa: E402,F401,F403
from benchmark.tests.test_kda_roofline import *  # noqa: E402,F401,F403
from benchmark.tests.test_moe_roofline import *  # noqa: E402,F401,F403
from benchmark.tests.test_moe_share_roofline import *  # noqa: E402,F401,F403
from benchmark.tests.test_program_spans import *  # noqa: E402,F401,F403
from benchmark.tests.test_reference import *  # noqa: E402,F401,F403
from benchmark.tests.test_reference_kimi_linear import *  # noqa: E402,F401,F403
from benchmark.tests.test_reference_qwen3_next import *  # noqa: E402,F401,F403
from benchmark.tests.test_request_timeline import *  # noqa: E402,F401,F403
from benchmark.tests.test_ssd_kinds import *  # noqa: E402,F401,F403
from benchmark.tests.test_ssd_roofline import *  # noqa: E402,F401,F403
from benchmark.tests.test_ssm_kinds import *  # noqa: E402,F401,F403
from benchmark.tests.test_ssm_roofline import *  # noqa: E402,F401,F403
from benchmark.tests.test_yardstick import *  # noqa: E402,F401,F403
