"""Plain float32 qwen2 with GSOFT adapters: the benchmark's reference.
It imports nothing of the program under test."""
