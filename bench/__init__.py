"""The chip benchmark: harness, drivers, references and readers."""
