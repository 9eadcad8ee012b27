"""Plain float32 references of the model families the benchmark runs.

They import nothing of the program under test.  ``common`` holds the
numerics and the layers both families share; ``<family>.py`` holds one
decoder layer of that family as the repository defines it; ``model`` holds
the embedding, the stack, the head, the loss and AdamW.
"""
