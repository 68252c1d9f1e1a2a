"""One file per model: parameters from a seed in the program's layout, the
plain float32 reference, and the work a training step requires."""
