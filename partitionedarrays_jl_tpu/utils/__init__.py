from .compile_cache import (
    compilation_cache_dir,
    enable_compilation_cache,
)
from .helpers import (
    AbstractMethodError,
    abstractmethod,
    check,
    checks_enabled,
    notimplemented,
    notimplementedif,
    unreachable,
)
from .table import (
    INDEX_DTYPE,
    Table,
    counts_to_ptrs,
    empty_table,
    generate_data_and_ptrs,
    get_data,
    get_ptrs,
    length_to_ptrs,
    ptrs_to_counts,
    rewind_ptrs,
)

__all__ = [
    "compilation_cache_dir",
    "enable_compilation_cache",
    "AbstractMethodError",
    "abstractmethod",
    "check",
    "checks_enabled",
    "notimplemented",
    "notimplementedif",
    "unreachable",
    "INDEX_DTYPE",
    "Table",
    "counts_to_ptrs",
    "empty_table",
    "generate_data_and_ptrs",
    "get_data",
    "get_ptrs",
    "length_to_ptrs",
    "ptrs_to_counts",
    "rewind_ptrs",
]
