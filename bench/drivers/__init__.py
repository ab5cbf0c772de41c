"""One driver per kind of cell, found by the name its traffic file gives."""
