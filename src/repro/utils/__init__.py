"""Small shared utilities (stage spans, CSR helpers)."""
from repro.utils.csr import CSR, csr_from_lists, invert_csr  # noqa: F401
