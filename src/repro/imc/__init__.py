# Version string of the IMC cost model's MATH (term structure, constants
# baked into the formulas — not TechParams, which travel per request).
# Bump on any change that can move a result bit for identical inputs; the
# service result cache (serve.cache.request_key) keys on it, so persisted
# entries from an older model can never be served against a newer one.
COST_MODEL_VERSION = "3"  # 3: the V/f check admits the nominal-point tie

from repro.imc.tech import TECH, TechParams  # noqa: F401
from repro.imc.cost import (  # noqa: F401
    DesignArrays,
    design_valid,
    evaluate_designs,
    evaluate_one,
)

# NOTE: repro.imc.tables (the factorized grid-table cost model) is imported
# lazily by its users, never here: tables depends on repro.core.space for
# the grid definitions and space depends on this package — importing it at
# package-init time would re-enter a partially-initialized module.
