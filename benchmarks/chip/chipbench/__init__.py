"""The chip benchmark's shared code."""
