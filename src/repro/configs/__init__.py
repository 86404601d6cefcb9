"""Partitioner presets: the paper's parameters (`buffcut_paper`)."""
