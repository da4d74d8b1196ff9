"""The benchmark's own yardstick: nothing here imports seaweedfs_tpu."""
