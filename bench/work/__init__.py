"""Operation and byte counts of the work the benchmark times."""
