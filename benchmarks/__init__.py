"""The benchmark of ray_tpu: BENCHMARK.json's harness, data and yardstick."""
