"""sfcheck — multi-pass static analyzer enforcing the repo's kernel/host
architecture invariants (CLAUDE.md, PARITY.md "Static analysis").

Passes (tools/sfcheck/passes/):

- **hotpath**       — no import-time jax.numpy dispatch, no wall-clock
                      reads inside ops/ functions (ex tools/lint_hotpath.py)
- **trace-hygiene** — no tracer concretization / host syncs in ops/
                      kernels (float(param), .item(), np.asarray(param),
                      jax.device_get, print)
- **fixed-shape**   — mask-don't-compact: no data-dependent-shape ops in
                      ops/ (nonzero/where/unique without size=, compress,
                      boolean-mask subscripts)
- **sync-discipline** — jax.block_until_ready banned everywhere outside
                      spatialflink_tpu/telemetry.py (every sync is an
                      accounted device→host fetch)
- **fstring-numpy** — float-formatted egress f-strings/.format must wrap
                      values in float()/int() (numpy ≥2 scalar reprs)

CLI: ``python -m tools.sfcheck [--pass NAME] [--json] [paths…]`` from the
repo root. Suppress a knowingly-fine line with ``# sfcheck: ok`` (all
passes) or ``# sfcheck: ok=<pass>`` plus a one-line justification.
Tier-1 enforcement: tests/test_sfcheck.py keeps the tree clean.
"""

from tools.sfcheck.core import (  # noqa: F401
    Finding,
    Report,
    check_file,
    check_source,
    default_targets,
    run_paths,
)
from tools.sfcheck.passes import ALL_PASSES, PASS_NAMES, get_pass  # noqa: F401
