"""The on-chip benchmark of deeplearning4j_tpu. See chipbench/README.md."""
