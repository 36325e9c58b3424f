"""Plain references of the engine's semantics, in NumPy.

Nothing here imports the program: a reference is handed the wave's
transactions and serialization order as inputs and says which lanes
commit, why the others abort, and what each wave installs.
"""
