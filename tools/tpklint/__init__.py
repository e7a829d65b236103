"""tpklint — the repo's by-convention invariants as tier-1 gates.

    python -m tools.tpklint [--rule NAME ...] [--root DIR] [--list-rules]

Rules (see each module's docstring for the full contract):

  host-sync        no host syncs inside `# tpk-hot:` regions
  spec-schema      generated schema artifacts match KNOBS tables
  lock-discipline  `# guarded-by:` fields only touched under their lock
  cpp-checked-io   fwrite/fsync/rename/ftruncate returns checked in cpp/
  ack-after-durable  server.cc releases staged acks only after the
                   covering group-commit fsync (markers pinned)
  metrics          tpk_* naming + README table sync (ex check_metrics.py)

Suppression: `# tpk-lint: allow(<rule>) reason=<why>` on the finding's
line or the line above; the reason is mandatory.
"""

from .core import (Context, Finding, PRAGMA_RULE, RULES, RULE_DOCS,
                   collect_pragmas, rule, run)

# Importing the rule modules registers them.
from . import rules_host_sync      # noqa: F401,E402
from . import rules_spec_schema    # noqa: F401,E402
from . import rules_lock           # noqa: F401,E402
from . import rules_cpp_io         # noqa: F401,E402
from . import rules_metrics        # noqa: F401,E402

__all__ = ["Context", "Finding", "PRAGMA_RULE", "RULES", "RULE_DOCS",
           "collect_pragmas", "rule", "run"]
