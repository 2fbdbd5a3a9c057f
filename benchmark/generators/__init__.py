"""Traffic generators, found by the name a mix file gives."""
