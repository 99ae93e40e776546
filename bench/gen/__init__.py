"""Traffic generators of the benchmark (copies, not imports, of the
program's generators)."""
